"""Jitted public wrappers for the Pallas kernels.

On the TPU the kernels compile for real.  On the CPU (tests and
rehearsals) they run in ``interpret=True`` mode — the kernel body
executes step by step, validating BlockSpec indexing and the numerics.
Any other backend is an error: silently interpreting there would hide
the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import quant_matmul as _qm
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here target the TPU (compiled) or the CPU "
        f"(interpreted); backend {backend!r} is neither")


def quant_matmul(x, wq, scale, out_dtype=jnp.bfloat16):
    return _qm.quant_matmul(x, wq, scale, out_dtype=out_dtype,
                            interpret=_interpret())


quantize_weights = _qm.quantize_weights


def flash_attention(q, k, v, *, scale, window: int = 0, softcap: float = 0.0):
    return _fa.flash_attention(q, k, v, scale=scale, window=window,
                               softcap=softcap, interpret=_interpret())


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *, scale,
                    softcap: float = 0.0, k_scale=None, v_scale=None):
    return _fa.paged_attention(q, k_pages, v_pages, block_tables, lengths,
                               scale=scale, softcap=softcap,
                               k_scale=k_scale, v_scale=v_scale,
                               interpret=_interpret())


def paged_extend_attention(q, k_pages, v_pages, k_new, v_new, block_tables,
                           pos, *, scale, softcap: float = 0.0,
                           k_scale=None, v_scale=None):
    return _fa.paged_extend_attention(q, k_pages, v_pages, k_new, v_new,
                                      block_tables, pos, scale=scale,
                                      softcap=softcap, k_scale=k_scale,
                                      v_scale=v_scale,
                                      interpret=_interpret())


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, h0=None):
    return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk, h0=h0,
                         interpret=_interpret())
