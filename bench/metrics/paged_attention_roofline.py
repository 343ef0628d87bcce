"""Paged decode kernel's share of its roofline (%): over the decode
waves wholly inside the profiler window, the least time the chip could
take for the kernel's calls (one per layer; the larger of operations
over peak and bytes over bandwidth, from ``bench/flops.py`` with each
row's live context) over the time of the kernel's events.  Moves
``itl_p95_ms``."""
import sys

from bench import flops


def read(rec):
    p, conf, peak = rec["profile"] or {}, rec["conf"], rec["peaks"]
    L = conf["num_hidden_layers"]
    least = spent = 0.0
    bound = {"compute": 0, "memory": 0}
    for s in p.get("steps", []):
        if s.get("kind") != "decode" or not s["kernel_s"] or not s["ctxs"]:
            continue
        fl, by = flops.paged_attention_call(conf, s["ctxs"])
        tc, tm = fl / peak["bf16_flops_per_s"], by / peak["hbm_bytes_per_s"]
        bound["compute" if tc > tm else "memory"] += 1
        least += L * max(tc, tm)
        spent += s["kernel_s"]
    if not spent:
        return None
    print(f"paged_attention_roofline: bound by {max(bound, key=bound.get)} "
          f"in {max(bound.values())} of {sum(bound.values())} waves",
          file=sys.stderr)
    return 100.0 * least / spent
