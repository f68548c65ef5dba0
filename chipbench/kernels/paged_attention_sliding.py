"""The sliding-attention calls of the ragged paged-attention kernel alone
(``ragged_paged_attention(..., window=)``): the kernel's ``name=`` carries
the window (``ragged_paged_attention_w4096``), which becomes its HLO
instruction's name, so a trace tells a windowed layer's call from a full
one's of the same shapes.  Matched by that name AND the shapes of
``kernels/paged_attention.py``; priced by that file's ``cost(window=)``.
"""

import re

from chipbench.kernels import paged_attention as base

NAME = "paged_attention_sliding"
_WINDOW = re.compile(r"^ragged_paged_attention_w(\d+)(\.\d+)?$")


def match(op):
    """Shapes and window of the call if ``op`` is a windowed call."""
    named = _WINDOW.match(op.name)
    shapes = base.match(op) if named else None
    return None if shapes is None else dict(shapes,
                                            window=int(named.group(1)))


def cost(rows, q_heads, kv_heads, head_dim, window, page_size,
         dtype_bytes: int = 2):
    """(flops, bytes) of one windowed layer's call for ``rows`` = [(q_len,
    context_len)]: what ``paged_attention.cost`` counts with the window."""
    return base.cost(rows, q_heads, kv_heads, head_dim,
                     dtype_bytes=dtype_bytes, window=window,
                     page_size=page_size)
