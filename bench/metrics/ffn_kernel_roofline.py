"""Kernels: the least time the chip needs for the FFN work of the
window's calls, over the summed device time of the fused tile-skip FFN
kernel, in %. The work of a call is its rows times the deployed
non-zero tiles of each layer (bench/work.py); its least time is the
larger of operations over peak rate and bytes over peak bandwidth.

The v5e trace names an operation by its HLO text, which for a Pallas
kernel holds no kernel name: the fused FFN kernel is the
``tpu_custom_call`` whose operands include the packed up-projection
blocks (nv, d_model, bf) and down-projection blocks (nv, bf, d_model).
"""
import re
import sys

import work


def is_ffn_kernel(name: str, d: int) -> bool:
    return ("tpu_custom_call" in name
            and re.search(rf"\[\d+,{d},\d+\]", name) is not None
            and re.search(rf"\[\d+,\d+,{d}\]", name) is not None)


def read(rec):
    m, peak = rec["model"], rec["peak"]
    spent = sum(v[1] for k, v in rec["trace"]["ops"].items()
                if is_ffn_kernel(k, m.d))
    if not spent:
        return None
    rows = [g * s for g, s in rec["prefill_shapes"]]
    rows += [rec["slots"]] * rec["decode_calls"]
    least = {"compute": 0.0, "memory": 0.0}
    for r in rows:
        for i in range(m.layers):
            c = m.ffn_call(i, r)
            t = work.least_time(c["flops"], c["bytes"], peak)
            least[t["bound"]] += t["s"]
    total = least["compute"] + least["memory"]
    print(f"[bench] ffn_kernel_roofline: least time {total:.6f} s "
          f"({least['memory']:.6f} s in memory-bound calls, "
          f"{least['compute']:.6f} s in compute-bound) over kernel time "
          f"{spent:.6f} s", file=sys.stderr)
    return 100.0 * total / spent
