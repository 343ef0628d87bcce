#!/usr/bin/env python3
"""Smoke run of the serving engine on one TPU chip.

    python chip_smoke.py

Runs in one process that owns the chip, and exits non-zero at once
when JAX finds no TPU: there is no CPU fallback.  Two phases, at
granite-moe-1b-a400m's full published width (``configs/
granite_moe_1b_a400m.py``, no dimension cut, random weights from seed
0):

1. **kernels** — the Pallas paged decode kernel (f32, bf16 — the
   served pool's dtype — and int8 pools) and the paged extend kernel
   (int8 pool), compiled for the chip, each against its
   ``kernels/ref.py`` oracle on a random permuted pool;
2. **served path** — ``EdgeServingEngine`` behind
   ``AsyncServingFrontend``: paged pool, radix prefix cache, Pallas
   paged decode (``use_pallas_paged=True``), 8 slots, ``max_len`` 2048,
   two prefill buckets.  Two rounds of 8 requests (prompts of 64-1024
   tokens, some past the largest bucket so extend catch-up waves run;
   the second round's turns share a system prompt with the first, so
   the prefix cache hits), 32 new tokens each.  It checks that every
   request finished with its tokens, that the pool's accounting holds,
   and that the compiled decode wave calls the kernel
   (``tpu_custom_call``), i.e. ran compiled, not interpreted.

Every time printed is a smoke timing of this one run, not a benchmark
number.  The last line of standard output is the device record
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import asyncio
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.serve import (AsyncServingFrontend,  # noqa: E402
                                enable_compile_cache)
from repro.models import layers as L  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving import EdgeServingEngine, Request, ServeConfig  # noqa: E402
from repro.serving.telemetry import default_clock  # noqa: E402

ARCH = "granite-moe-1b-a400m"
MAX_NEW = 32

# Largest absolute error allowed between a kernel and its oracle.  The
# oracle runs at "highest" matmul precision; the kernel's f32 dots may
# run as single bf16 MXU passes, which round each operand to 8 mantissa
# bits (relative 2^-8).  With q, k, v ~ N(0, 0.25) and head_dim 64 a
# logit q.k * 64^-0.5 then errs by about 2^-8 * sum|q_i k_i| / 8 ~ 5e-3,
# which moves the softmax-weighted mean of |v| <~ 2 by about 1e-2.  The
# bf16 and int8 cases read the same stored bytes (and scales) on both
# sides, so the same bound holds.
KERNEL_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def require_tpu():
    """The first device, which must be a TPU; exit 2 otherwise."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev.platform!r}); this script runs only on the chip",
              file=sys.stderr)
        sys.exit(2)
    return dev


# ------------------------------------------------------------- kernels
def kernel_phase(*, B: int, H: int, K: int, hd: int, bs: int, n_blk: int,
                 S: int, seed: int = 0) -> dict:
    """Run the paged kernels through ``kernels.ops`` on a random pool of
    ``B * n_blk`` pages with permuted block tables, ragged lengths and
    unallocated (-1) tails, and return each one's largest absolute
    error against its ``kernels.ref`` oracle."""
    rng = np.random.default_rng(seed)
    nB = B * n_blk
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (B, H, hd)) * 0.5
    kp = jax.random.normal(ks[1], (nB, bs, K, hd)) * 0.5
    vp = jax.random.normal(ks[2], (nB, bs, K, hd)) * 0.5
    qe = jax.random.normal(ks[3], (B, S, H, hd)) * 0.5
    kn = jax.random.normal(ks[4], (B, S, K, hd)) * 0.5
    vn = jax.random.normal(ks[5], (B, S, K, hd)) * 0.5
    lengths = rng.integers(1, n_blk * bs + 1, B).astype(np.int32)
    lengths[0] = n_blk * bs                       # one row fills its table
    perm = rng.permutation(nB).astype(np.int32)
    tables = np.full((B, n_blk), -1, np.int32)
    for b in range(B):
        n = -(-int(lengths[b]) // bs)
        tables[b, :n] = perm[b * n_blk:b * n_blk + n]
    bt, ln = jnp.asarray(tables), jnp.asarray(lengths)
    kq, ksc = L.quantize_kv(kp)
    vq, vsc = L.quantize_kv(vp)
    kw = dict(scale=hd ** -0.5)
    q8 = dict(kw, k_scale=ksc, v_scale=vsc)
    kb, vb = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    decode = ops.paged_attention
    got = {
        "paged_attention_f32": decode(q, kp, vp, bt, ln, **kw),
        "paged_attention_bf16": decode(q, kb, vb, bt, ln, **kw),
        "paged_attention_int8": decode(q, kq, vq, bt, ln, **q8),
        "paged_extend_int8": ops.paged_extend_attention(
            qe, kq, vq, kn, vn, bt, ln, **q8),
    }
    with jax.default_matmul_precision("highest"):
        want = {
            "paged_attention_f32": ref.paged_attention_ref(
                q, kp, vp, bt, ln, **kw),
            "paged_attention_bf16": ref.paged_attention_ref(
                q, kb, vb, bt, ln, **kw),
            "paged_attention_int8": ref.paged_attention_ref(
                q, kq, vq, bt, ln, **q8),
            "paged_extend_int8": ref.paged_extend_attention_ref(
                qe, kq, vq, kn, vn, bt, ln, **q8),
        }
    errs = {}
    for name, out in got.items():
        check(out.shape == want[name].shape,
              f"{name}: shape {out.shape} != {want[name].shape}")
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - want[name].astype(jnp.float32))))
        check(np.isfinite(err), f"{name}: non-finite output")
        errs[name] = err
    return errs


# --------------------------------------------------------- served path
def make_rounds(vocab: int, buckets: tuple, seed: int = 0) -> list:
    """Two rounds of 8 requests, sized from the prefill buckets
    ``(b0, b1)`` so that each round admits in groups of 4 per bucket and
    the programs of round 1 serve round 2:

    * round 1: 4 prompts in ``b0``; 2 turns of ``sys`` + user, and 2
      prompts longer than ``b1`` (extend catch-up) in ``b1``;
    * round 2: 4 new turns on the same ``sys`` (prefix-cache hits whose
      suffix prefills in ``b0``) and 4 cold prompts in ``b1``, two of
      them longer than ``b1``.
    """
    b0, b1 = buckets
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, 3 * b1 // 4, dtype=np.int32)

    def turn():
        return np.concatenate(
            [system, rng.integers(0, vocab, b0 // 4, dtype=np.int32)])

    def cold(n):
        return rng.integers(0, vocab, n, dtype=np.int32)

    r1 = ([cold(n) for n in (b0 // 4, b0 // 2, 3 * b0 // 4, b0)]
          + [turn(), turn(), cold(3 * b1 // 2), cold(2 * b1)])
    r2 = ([turn() for _ in range(4)]
          + [cold(n) for n in (b0 + b0 // 4, b1, 5 * b1 // 4, 7 * b1 // 4)])
    uid = iter(range(16))
    return [[Request(uid=next(uid), prompt=p, max_new_tokens=MAX_NEW)
             for p in r] for r in (r1, r2)]


async def _serve_rounds(eng: EdgeServingEngine, rounds: list) -> dict:
    """Serve each round through the frontend, streaming every request's
    tokens; the next round starts when the previous one is done."""
    fe = AsyncServingFrontend(eng)
    await fe.start()
    streamed = {}

    async def collect(h):
        streamed[h.uid] = [tok async for tok in h]

    for reqs in rounds:
        handles = [fe.submit(r) for r in reqs]
        await asyncio.gather(*(collect(h) for h in handles))
        for h in handles:
            await h.done
    await fe.shutdown()
    return streamed


def decode_wave_hlo(eng: EdgeServingEngine) -> str:
    """Compiled HLO text of the engine's decode wave at its live shapes
    (the program every one-token wave runs)."""
    compiled = eng._decode.lower(
        eng.params, eng.cache, jnp.asarray(eng.tokens),
        jnp.asarray(eng.pos), jnp.asarray(eng.temps),
        jnp.asarray(eng.topks), jax.random.PRNGKey(0),
        jnp.asarray(eng.block_tables), any_topk=False).compile()
    return compiled.as_text()


def served_phase(cfg, scfg: ServeConfig, seed: int = 0) -> dict:
    """Build the engine from ``cfg`` with random weights, serve
    ``make_rounds`` through ``AsyncServingFrontend`` and check what came
    out.  Returns counts, smoke timings and the decode wave's HLO."""
    t0 = default_clock()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    eng = EdgeServingEngine(cfg, params, scfg)
    jax.block_until_ready(eng.cache)
    t_setup = default_clock() - t0
    rounds = make_rounds(cfg.vocab_size, scfg.prefill_buckets, seed)
    reqs = [r for rnd in rounds for r in rnd]

    t1 = default_clock()
    streamed = asyncio.run(_serve_rounds(eng, rounds))
    t_serve = default_clock() - t1

    for r in reqs:
        check(r.done and not r.cancelled, f"request {r.uid} did not finish")
        check(len(r.generated) == r.max_new_tokens,
              f"request {r.uid}: {len(r.generated)} tokens, "
              f"want {r.max_new_tokens}")
        check(streamed.get(r.uid) == list(r.generated),
              f"request {r.uid}: streamed tokens differ from generated")
        check(all(0 <= t < cfg.vocab_size for t in r.generated),
              f"request {r.uid}: token id outside the vocabulary")
    check(not eng.active.any() and not eng.queue, "engine not drained")
    eng.pool.assert_consistent()
    st = eng.stats()
    n_hits = len(rounds[1]) // 2
    check(st["prefix_hits"] >= n_hits,
          f"prefix cache hits {st['prefix_hits']} < {n_hits}")
    return {
        "requests": len(reqs),
        "tokens": sum(len(r.generated) for r in reqs),
        "prompt_tokens": sum(len(r.prompt) for r in reqs),
        "prefix_hits": st["prefix_hits"],
        "prefix_hit_tokens": st["prefix_hit_tokens"],
        "steps": eng.steps,
        "setup_s": t_setup,
        "serve_s": t_serve,
        "decode_hlo": decode_wave_hlo(eng),
    }


# ---------------------------------------------------------------- main
class CompileWatch:
    """Seconds spent in backend compilation (a persistent-cache hit
    counts only its read) plus cache hit/miss counts, from JAX's
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def main() -> None:
    dev = require_tpu()
    cache_dir = enable_compile_cache()
    watch = CompileWatch()
    cfg = get_config(ARCH)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache_dir}")

    errs = kernel_phase(B=8, H=cfg.num_heads, K=cfg.num_kv_heads,
                        hd=cfg.head_dim, bs=16, n_blk=128, S=4)
    for name, err in errs.items():
        print(f"kernel {name}: max abs err {err:.3e} (tol {KERNEL_TOL:.0e})")
        check(err <= KERNEL_TOL, f"kernel {name}: error {err:.3e} "
              f"exceeds {KERNEL_TOL:.0e}")

    scfg = ServeConfig(max_slots=8, max_len=2048, prefill_buckets=(256, 512),
                       kv_block_size=16, prefix_cache=True,
                       use_pallas_paged=True, catch_chunk=16)
    out = served_phase(cfg, scfg)
    n_custom = out.pop("decode_hlo").count("tpu_custom_call")
    check(n_custom > 0, "decode wave HLO has no tpu_custom_call: the paged "
          "kernel did not run compiled")
    print(f"served {ARCH} (d_model {cfg.d_model}, {cfg.num_layers} layers, "
          f"{cfg.num_experts} experts top-{cfg.num_experts_per_tok}, "
          f"vocab {cfg.vocab_size}): {out['requests']} requests, "
          f"{out['prompt_tokens']} prompt tokens, {out['tokens']} new "
          f"tokens in {out['steps']} engine steps")
    print(f"prefix cache: {out['prefix_hits']} hits, "
          f"{out['prefix_hit_tokens']} tokens served from cache")
    print(f"decode wave: {n_custom} tpu_custom_call site(s) in compiled HLO")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    print(f"smoke timings (one run, not a benchmark): set-up "
          f"{out['setup_s']:.1f} s, serving {out['serve_s']:.1f} s, "
          f"compile {watch.seconds:.1f} s "
          f"(persistent cache: {watch.cache_hits} hits, "
          f"{watch.cache_misses} misses)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
