"""Granite MoE (``model_type`` "granitemoe"): the Llama block of
``llama.py``, whose feed-forward is the top-k mixture of SwiGLU experts
when the configuration states ``num_local_experts``."""

from arch.llama import *  # noqa: F401,F403
