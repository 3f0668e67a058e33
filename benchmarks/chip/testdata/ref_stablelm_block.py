"""Test-only reference: declares StableLM-2's block.

It computes nothing of its own; the tests load it to see a configuration
that states StableLM-2's four block keys (``layer_norm_eps``,
``qk_layernorm``, ``use_parallel_residual``, ``partial_rotary_factor``)
accepted under a reference that declares them, and refused under
``ref.py``.
"""
from ref import BLOCK as LLAMA_BLOCK

BLOCK = dict(LLAMA_BLOCK, norm="layer", qk_norm=True, parallel_residual=True,
             rotary_fraction=0.25)
