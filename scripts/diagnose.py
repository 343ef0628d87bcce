import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# a CPU-only tool: never start the TPU backend (it would hold the chip)
os.environ["JAX_PLATFORMS"] = "cpu"

"""Hillclimb diagnostics: compile one (arch x shape) and print the top
ops by bytes / flops / collective bytes (trip-scaled, per chip).

  PYTHONPATH=src python scripts/diagnose.py <arch> <shape> [top]
  PYTHONPATH=src python scripts/diagnose.py --spec [verify] [draft] \
      [gamma] [max_len]   # draft/verify speculative compatibility
  PYTHONPATH=src python scripts/diagnose.py --cache [store.npz]
      # per-arch prefix-sharing capability; with a path, also a
      # persisted prefix-store report (header + per-chain summary)
  PYTHONPATH=src python scripts/diagnose.py --server [arch]
      # step-driven serving introspection: wave-budget plans,
      # live-slot frontier table, frontend TTFT / ITL percentiles
  PYTHONPATH=src python scripts/diagnose.py --quant
      # per-arch quantization surface (int8 KV-poolable? draft-weight
      # quantizable?) + fused dequant kernel vs reference parity verdict
  PYTHONPATH=src python scripts/diagnose.py --trace trace.json
      # summarize a serving trace dump (launch.serve --trace /
      # engine.dump_chrome_trace): top phases by total time,
      # per-request TTFT decomposition table, spec acceptance by round

Exit codes (uniform across modes so CI can gate on any of them):
  0  report printed, all verdicts OK
  1  failure verdict — spec pairing incompatible (--spec), prefix
     store unreadable/corrupt (--cache), kernel parity FAIL (--quant),
     engine failed to drain or budget overshot (--server), trace
     invalid or structurally broken (--trace)
"""
import json
import sys

from repro.configs import INPUT_SHAPES, get_config
from repro.launch import presets as pz
from repro.launch import specs as sp
from repro.launch.hlo_analysis import breakdown
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.training import optimizer as opt
from repro.training import trainer as tr


def spec_report(args: list) -> None:
    """Per-arch speculative capabilities + a draft/verify pairing
    verdict (vocab match, verify spec_decodable, gamma bounds) via the
    same ``validate_spec`` the engine enforces."""
    from repro.configs.registry import ARCH_IDS
    from repro.configs import get_smoke_config
    from repro.serving.spec_decode import validate_spec
    caps = {}
    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        caps[arch] = {
            "family": cfg.family,
            "vocab": cfg.vocab_size,
            "extendable": M.extendable(cfg),       # multi-token catch-up
            "spec_decodable": M.spec_decodable(cfg),  # verify-capable
        }
    print("spec capabilities:", json.dumps(caps, indent=1))
    verify = args[0] if len(args) > 0 else "phi3-medium-14b"
    draft = args[1] if len(args) > 1 else "gemma3-1b"
    gamma = int(args[2]) if len(args) > 2 else 4
    max_len = int(args[3]) if len(args) > 3 else 256
    problems = validate_spec(get_smoke_config(verify),
                             get_smoke_config(draft), gamma, max_len)
    print(f"pairing verify={verify} draft={draft} gamma={gamma} "
          f"max_len={max_len}:")
    if problems:
        for p in problems:
            print(f"  INCOMPATIBLE: {p}")
        sys.exit(1)
    print("  ok (vocab match, verify spec_decodable, gamma in bounds)")


def cache_report(args: list) -> None:
    """Prefix-sharing capability per arch + (optionally) a persisted
    prefix-store report: validates the header the same way the engine
    does at rehydrate time and summarizes the stored chains."""
    from repro.configs.registry import ARCH_IDS
    from repro.configs import get_smoke_config
    caps = {arch: {"family": get_smoke_config(arch).family,
                   "prefix_sharable": M.prefix_sharable(
                       get_smoke_config(arch))}
            for arch in ARCH_IDS}
    print("prefix-sharing capabilities:", json.dumps(caps, indent=1))
    if not args:
        return
    import numpy as np
    path = args[0]
    try:
        with np.load(path) as data:
            meta = json.loads(bytes(data["meta"]).decode())
            n = int(data["n_chains"])
            chains = []
            total_blocks = 0
            for i in range(n):
                key = data[f"key_{i}"]
                nb = data[f"pages_{i}_0"].shape[1] if n else 0
                total_blocks += nb
                chains.append({"namespace": int(data[f"ns_{i}"]),
                               "tokens": int(len(key)), "blocks": int(nb)})
    except Exception as e:   # same operator-facing verdict as the engine
        print(f"prefix store {path}: UNREADABLE/CORRUPT ({e!r}) — an "
              "engine pointed at it will reject it and start cold")
        sys.exit(1)
    print(f"prefix store {path}:")
    print("  header:", json.dumps(meta))
    print(f"  chains: {n}, total blocks: {total_blocks}")
    for i, c in enumerate(chains[:16]):
        print(f"  chain {i}: {c['tokens']} tokens / {c['blocks']} pages "
              f"(namespace {c['namespace']})")
    if n > 16:
        print(f"  ... and {n - 16} more")


def quant_report(args: list) -> None:
    """Quantization surface per arch + a kernel parity verdict.

    Table: does the family hold int8-poolable KV pages (probed the same
    way the engine builds its pool — ``init_paged_cache`` with
    ``kv_dtype="int8"`` then checking for scale leaves), and are its
    weights draft-quantizable (``quantize_matmul_params`` finds matmul
    leaves to rewrite)?  Then the fused dequant paged-attention kernels
    (decode + extend) are run against the gather+dequant reference and
    the max logit error becomes the operator-facing verdict; exits 1 on
    parity failure so CI can gate on it.
    """
    del args
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import ARCH_IDS
    from repro.configs import get_smoke_config
    from repro.kernels import ops
    from repro.kernels import ref as R
    from repro.models import layers as L

    def has_quant_pages(tree) -> bool:
        if isinstance(tree, dict):
            if L.kv_pages_quantized(tree):
                return True
            return any(has_quant_pages(v) for v in tree.values())
        return False

    def count_quant_leaves(tree) -> int:
        if isinstance(tree, dict):
            if "q" in tree and "scale" in tree:
                return 1
            return sum(count_quant_leaves(v) for v in tree.values())
        return 0

    caps = {}
    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        pages = M.init_paged_cache(cfg, 1, 32, num_blocks=4,
                                   block_size=8, kv_dtype="int8")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        qp = L.quantize_matmul_params(params)
        caps[arch] = {"family": cfg.family,
                      "kv_poolable_int8": has_quant_pages(pages),
                      "draft_quant_leaves": count_quant_leaves(qp)}
    print("quantization surface:", json.dumps(caps, indent=1))

    # --- fused dequant kernel vs gather+dequant reference --------------
    key = jax.random.PRNGKey(7)
    B, H, K, hd, nB, bs, n_blk, S = 2, 4, 2, 64, 12, 8, 3, 4
    ks = jax.random.split(key, 5)
    kf = jax.random.normal(ks[0], (nB, bs, K, hd), jnp.float32)
    vf = jax.random.normal(ks[1], (nB, bs, K, hd), jnp.float32)
    kq, ksc = L.quantize_kv(kf)
    vq, vsc = L.quantize_kv(vf)
    bt = jnp.arange(B * n_blk, dtype=jnp.int32).reshape(B, n_blk)
    pos = jnp.asarray([bs * n_blk - 1, 13], jnp.int32)
    scale = hd ** -0.5
    qd = jax.random.normal(ks[2], (B, H, hd), jnp.float32)
    out_k = ops.paged_attention(qd, kq, vq, bt, pos, scale=scale,
                                k_scale=ksc, v_scale=vsc)
    out_r = R.paged_attention_ref(qd, kq, vq, bt, pos, scale=scale,
                                  k_scale=ksc, v_scale=vsc)
    err_d = float(jnp.max(jnp.abs(out_k - out_r)))
    qe = jax.random.normal(ks[3], (B, S, H, hd), jnp.float32)
    kn = jax.random.normal(ks[4], (B, S, K, hd), jnp.float32)
    vn = jax.random.normal(ks[0], (B, S, K, hd), jnp.float32)
    ext_k = ops.paged_extend_attention(qe, kq, vq, kn, vn, bt, pos,
                                       scale=scale, k_scale=ksc,
                                       v_scale=vsc)
    ext_r = R.paged_extend_attention_ref(qe, kq, vq, kn, vn, bt, pos,
                                         scale=scale, k_scale=ksc,
                                         v_scale=vsc)
    err_e = float(jnp.max(jnp.abs(ext_k - ext_r)))
    tol = 2e-5
    ok = err_d < tol and err_e < tol
    print(f"fused dequant kernel parity: decode err {err_d:.2e}, "
          f"extend err {err_e:.2e}, tol {tol:.0e} -> "
          f"{'OK' if ok else 'FAIL'}")
    if not ok:
        sys.exit(1)


def server_report(args: list) -> None:
    """Step-driven serving introspection: drive a live chunked engine a
    few waves and print each wave's budget plan (slot -> mode x width),
    the live-slot frontier table mid-flight, then finish the trace
    through the always-on frontend and report TTFT and inter-token
    latency p50/p99 from this function's own submit stamps."""
    import asyncio

    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.serve import AsyncServingFrontend, latency_stats
    from repro.serving import EdgeServingEngine, Request, ServeConfig
    from repro.serving.telemetry import default_clock

    arch = args[0] if args else "phi3-medium-14b"
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = EdgeServingEngine(cfg, params, ServeConfig(
        max_slots=3, max_len=96, prefill_buckets=(8, 16, 32),
        chunked_prefill=True, catch_chunk=4, wave_tokens=10))
    rng = np.random.default_rng(0)

    def req(uid, n):
        return Request(uid=uid,
                       prompt=rng.integers(0, cfg.vocab_size, n,
                                           dtype=np.int32),
                       max_new_tokens=8)

    for uid, n in enumerate((30, 6, 12)):   # one catch-up + two short
        eng.submit(req(uid, n))
    print(f"wave-budget plans ({arch}, wave_tokens=10, catch_chunk=4):")
    overshoots = []
    for i in range(4):
        eng.step()
        plan = {s: f"{m}x{v}" for s, (m, v) in sorted(eng.last_plan.items())}
        fed = sum(v for _, v in eng.last_plan.values())
        if fed > eng.scfg.wave_tokens:
            overshoots.append((i, fed))
        print(f"  wave {i}: {json.dumps(plan)}")
    print("live-slot frontier:")
    print("  slot uid   pos pending published mode")
    for s in range(eng.scfg.max_slots):
        r = eng.slot_req[s]
        if r is None or not eng.active[s]:
            continue
        pend = 0 if eng.pending[s] is None else len(eng.pending[s])
        mode = eng.last_plan.get(s, ("-", 0))[0]
        print(f"  {s:4d} {r.uid:3d} {int(eng.pos[s]):5d} {pend:7d} "
              f"{eng.slot_published[s]:9d} {mode}")

    async def finish():
        fe = AsyncServingFrontend(eng)
        await fe.start()
        submitted = [(default_clock(), fe.submit(req(10, 9))),
                     (default_clock(), fe.submit(req(11, 21)))]
        await fe.shutdown()                  # drains everything live
        return latency_stats(submitted)

    print("frontend latency (ms):", json.dumps(asyncio.run(finish())))
    st = eng.stats()
    print("engine:", json.dumps({k: st[k] for k in
                                 ("steps", "mixed_waves", "wave_admitted",
                                  "cancels")}))
    # operator verdict: every submitted request drained, no wave ever
    # exceeded its token budget
    done = sorted(r.uid for r in eng.completed)
    expect = [0, 1, 2, 10, 11]
    ok = done == expect and not overshoots
    print(f"server verdict: drained {done} (expect {expect}), "
          f"budget overshoots {overshoots} -> {'OK' if ok else 'FAIL'}")
    if not ok:
        sys.exit(1)


def trace_report(args: list) -> None:
    """Summarize a Chrome-trace dump produced by
    ``launch.serve --trace`` / ``engine.dump_chrome_trace``: validity
    verdict, top phases by total time, per-request TTFT decomposition,
    and speculative acceptance by round.  Exits 1 when the file is
    unreadable or structurally invalid (missing ph/ts/pid/tid,
    unbalanced B/E spans)."""
    from repro.serving.telemetry import summarize_trace

    if not args:
        print("usage: diagnose.py --trace <trace.json>")
        sys.exit(1)
    path = args[0]
    try:
        with open(path) as f:
            trace = json.load(f)
    except Exception as e:
        print(f"trace {path}: UNREADABLE ({e!r})")
        sys.exit(1)
    s = summarize_trace(trace)
    n_ev = len(trace.get("traceEvents", []))
    print(f"trace {path}: {n_ev} events, "
          f"{len(s['requests'])} requests")
    print("top phases by total time:")
    print("  phase             total_ms   calls   mean_us")
    for p in s["phases"][:10]:
        print(f"  {p['name']:<16s} {p['total_us'] / 1e3:9.3f} "
              f"{p['calls']:7d} {p['mean_us']:9.1f}")
    if s["requests"]:
        print("per-request TTFT decomposition (ms):")
        print("  uid    queue  prefill  first_wave    ttft     e2e  toks")
        for r in s["requests"]:
            def ms(v):
                return "     -" if v is None else f"{v / 1e3:6.2f}"
            print(f"  {r['uid']:3d} {ms(r['queue_wait_us'])} "
                  f"{ms(r['prefill_us'])}   {ms(r['first_wave_us'])} "
                  f" {ms(r['ttft_us'])} {ms(r['e2e_us'])} "
                  f"{r['n_tokens']:5d}")
    if s["accept_by_round"]:
        print("spec acceptance by round position:")
        for j, row in s["accept_by_round"].items():
            print(f"  round[{j}]: {row['accepted']}/{row['proposed']} "
                  f"accepted ({row['rate']:.2f})")
    if s["problems"]:
        for p in s["problems"][:20]:
            print(f"  INVALID: {p}")
        print(f"trace verdict: FAIL ({len(s['problems'])} problems)")
        sys.exit(1)
    print("trace verdict: OK")


def main():
    if "--trace" in sys.argv:
        trace_report([a for a in sys.argv[1:] if not a.startswith("-")])
        return
    if "--quant" in sys.argv:
        quant_report([a for a in sys.argv[1:] if not a.startswith("-")])
        return
    if "--server" in sys.argv:
        server_report([a for a in sys.argv[1:] if not a.startswith("-")])
        return
    if "--cache" in sys.argv:
        cache_report([a for a in sys.argv[1:] if not a.startswith("-")])
        return
    if "--spec" in sys.argv:
        spec_report([a for a in sys.argv[1:] if not a.startswith("-")])
        return
    if len(sys.argv) < 3:
        return
    arch, shape_name = sys.argv[1], sys.argv[2]
    top = int(sys.argv[3]) if len(sys.argv) > 3 else 10
    preset_name = sys.argv[4] if len(sys.argv) > 4 else "baseline"
    preset = (pz.baseline if preset_name == "baseline" else pz.optimized)(arch)
    shape = INPUT_SHAPES[shape_name]
    cfg = M.specialize(get_config(arch), shape).replace(
        param_dtype=preset.param_dtype,
        moe_rowwise=getattr(preset, "moe_rowwise", False))
    mesh = make_production_mesh()
    tcfg = tr.TrainConfig(
        optimizer=opt.OptimizerConfig(moments_dtype=preset.moments_dtype),
        microbatches=preset.microbatches, remat=preset.remat)
    built = sp.build(cfg, shape, mesh, tcfg=tcfg, fsdp=preset.fsdp,
                     smart=preset.smart)
    compiled = built.fn.lower(*built.args).compile()
    bd = breakdown(compiled.as_text(), top=top)
    for section in ("by_coll", "by_bytes", "by_flops"):
        print(f"\n==== {section} ====")
        for r in bd[section]:
            key = {"by_coll": "coll_bytes", "by_bytes": "bytes",
                   "by_flops": "flops"}[section]
            print(f"  {r[key]:.3e}  x{r['scale']:<6.0f} {r['opcode']:<22s} "
                  f"{r['shape']:<40s} {r['meta']}")


if __name__ == "__main__":
    main()
