"""Model files on the shared parallel substrate.  ``gpt2``, ``llama`` and
``vit`` are models (init_params / logical_axes / loss_fn / make_train_step);
``moe`` is not a model but the dropless mixture-of-experts layer that
``llama`` puts in place of its SwiGLU MLP when the configuration has experts
(OLMoE-1B-7B); ``layers`` holds the two halves of a decoder layer that
``llama`` and ``hybrid`` share.  ``hybrid`` (a decoder that is a list of layer
kinds: Nemotron-3-Nano, Solar-Open2, JoyAI-LLM-Flash) and its Mamba-2 mixer ``mamba2`` are not
imported here: they, and ``ops/ssd.py`` behind them, load when such a model
is built; its KDA mixer ``kda``, and ``ops/kda.py`` behind it, load with the
first pattern that holds a ``K``, its latent-attention mixer ``mla`` and its
dense MLP layer ``dense`` (JoyAI-LLM-Flash) with the first that holds an ``L``
or a ``D``."""

from ray_tpu.models import gpt2, llama, moe, vit

__all__ = ["gpt2", "llama", "moe", "vit"]
