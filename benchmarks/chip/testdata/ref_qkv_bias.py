"""Test-only reference: declares a block with biased q/k/v projections.

It computes nothing of its own; the tests load it to see a configuration
that states ``use_qkv_bias: true`` accepted under a reference that
declares it, and refused under ``ref.py``.
"""
from ref import BLOCK as LLAMA_BLOCK

BLOCK = dict(LLAMA_BLOCK, qkv_bias=True)
