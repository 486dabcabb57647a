"""Public wrappers for the Pallas kernels.

On CPU the kernels execute in ``interpret=True`` mode — the kernel body
runs as traced JAX ops, bit-for-bit matching the TPU lowering semantics.
On TPU backends the compiled kernels run natively.  The DC kernels pad
the batch to their tile themselves (`genasm_dc.batch_tile`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bitalign as _ba
from . import genasm_dc as _gdc
from . import genasm_dc_v2 as _v2
from . import myers as _my


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def window_dc(sub_texts, sub_patterns, *, w: int = 64, k: int = 24,
              block_bt: int | None = None):
    """GenASM-DC over a batch of windows (Pallas kernel).

    ``sub_texts``/``sub_patterns``: [B, w] int8.  Returns
    ``(d_min [B], tb [B, w, k+1, 3, nw])``.
    """
    return _gdc.window_dc_batch(sub_texts, sub_patterns, w=w, k=k,
                                block_bt=block_bt or _gdc.DEFAULT_BT,
                                interpret=_interpret())


def window_dc_v2(sub_texts, sub_patterns, *, w: int = 64, k: int = 24,
                 block_bt: int | None = None):
    """v2 kernel: R-only TB store (3× smaller; see genasm_dc_v2)."""
    return _v2.window_dc_batch_v2(sub_texts, sub_patterns, w=w, k=k,
                                  block_bt=block_bt or _gdc.DEFAULT_BT,
                                  interpret=_interpret())


def bitalign_dc(bases, succ_bits, patterns, p_lens, *, m_bits: int, k: int,
                block_bt: int | None = None):
    """Batched BitAlign DC kernel."""
    return _ba.bitalign_dc_batch(bases, succ_bits, patterns, p_lens,
                                 m_bits=m_bits, k=k,
                                 block_bt=block_bt or _gdc.DEFAULT_BT,
                                 interpret=_interpret())


def myers_distance(texts, patterns, m_lens, *, m_bits: int, mode: str = "global",
                   block_bt: int | None = None):
    """Batched Myers edit distance (Pallas kernel, padded to tile)."""
    b = texts.shape[0]
    bt = block_bt or min(128, max(8, b))
    pad = (-b) % bt
    if pad:
        texts = jnp.concatenate([texts, jnp.full((pad, texts.shape[1]), 4, texts.dtype)])
        patterns = jnp.concatenate(
            [patterns, jnp.full((pad, patterns.shape[1]), 4, patterns.dtype)]
        )
        m_lens = jnp.concatenate([m_lens, jnp.ones((pad,), m_lens.dtype)])
    out = _my.myers_distance_batch(
        texts, patterns, m_lens, m_bits=m_bits, mode=mode, block_bt=bt,
        interpret=_interpret(),
    )
    return out[:b]
