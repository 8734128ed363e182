"""jit'd public wrappers around the Pallas kernels.

The kernels run compiled on an accelerator. They run in Pallas interpret
mode only when the program is traced for the CPU, which has no Mosaic
lowering: that is how the CPU tests check them against ``kernels/ref.py``.
The target is the device kind of the mesh the program is traced under
(``jax.set_mesh``), so a step traced for described, compile-only TPU
devices gets the TPU's kernels; without a mesh, the default device's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_adam as _ad
from repro.kernels import tiled_matmul as _mm

LANE = _ad.LANE


def _target_kind() -> str:
    dev = jax.sharding.get_abstract_mesh().abstract_device
    return dev.device_kind if dev is not None else jax.devices()[0].device_kind


def on_tpu() -> bool:
    """Whether the program being traced compiles for a TPU."""
    return _target_kind().startswith("TPU")


def _interpret() -> bool:
    return _target_kind() == "cpu"


def fused_adam(p32, g32, m, v, *, lr, beta1, beta2, eps, weight_decay, bc1, bc2,
               block_rows: int = _ad.DEFAULT_BLOCK_ROWS):
    """Flat fused Adam over an arbitrary-shaped leaf. Returns (p32, m, v)
    shaped like the input (the bf16 copy is returned via .astype by callers
    that want it; see optim/adam.py)."""
    shape = p32.shape
    n = p32.size
    pad = (-n) % LANE

    def flat(x):
        x = x.reshape(-1).astype(jnp.float32)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(-1, LANE)

    scalars = jnp.stack([lr, jnp.float32(beta1), jnp.float32(beta2),
                         jnp.float32(eps), jnp.float32(weight_decay),
                         bc1, bc2]).astype(jnp.float32)
    p2, m2, v2, _ = _ad.fused_adam_flat(flat(p32), flat(g32), flat(m), flat(v),
                                        scalars, block_rows=block_rows,
                                        interpret=_interpret())

    def unflat(x):
        return x.reshape(-1)[:n].reshape(shape)

    return unflat(p2), unflat(m2), unflat(v2)


def tiled_matmul(x, w, **kw):
    return _mm.tiled_matmul(x, w, interpret=_interpret(), **kw)


def quantized_matmul(x, q, scales, **kw):
    """Fused dequant-matmul on q8 wire operands (int8 quants + per-block
    fp16 scales, see ``core/qformat.py``): the full-precision weight never
    materializes in HBM — tiles dequantize in VMEM ahead of the MXU dot."""
    return _mm.quantized_matmul(x, q, scales, interpret=_interpret(), **kw)


def flash_attention(q, k, v, *, causal=True, **kw):
    """Differentiable causal GQA flash attention, (B, H, S, D) layout."""
    return _fa.flash_attention(q, k, v, causal=causal, interpret=_interpret(),
                               **kw)
