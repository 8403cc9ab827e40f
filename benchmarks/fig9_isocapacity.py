"""Fig. 9 — iso-capacity analysis: 2^16 TCAM cells per array.

Subarray size varies 16x16 (256 subarrays/array) .. 256x256 (1
subarray/array) with the per-array cell capacity fixed; mats/bank and
arrays/mat as before.  Note these designs are NOT iso-area (smaller
subarrays need more peripherals).

Paper observations reproduced:
* iso-base energy is nearly constant across subarray sizes,
* execution time varies in a moderate band (58us @16x16 -> 150us @256x256
  for 10k queries) — grows with column count despite constant cells/array,
* cam-density / cam-power+density average ~1.75x energy improvement over
  iso-base except at 128/256,
* power drops significantly under the density/power+density transforms.
"""

from __future__ import annotations

import numpy as np

from repro.core import ArchSpec, compile_fn

from .common import banner, save_json, table

CELLS_PER_ARRAY = 2 ** 16


def hdc_kernel(inp, weight):
    others = weight.transpose(-2, -1)
    mm = inp.matmul(others)
    return mm.topk(1, largest=False)


def run(n_queries: int = 10_000, dim: int = 8192, n_classes: int = 10):
    banner("Fig. 9 — iso-capacity (2^16 cells/array)")
    rows = []
    results = {}
    for mode, target in (("iso-base", "latency"),
                         ("cam-density", "density"),
                         ("cam-power+density", "power+density")):
        for s in (16, 32, 64, 128, 256):
            subs = CELLS_PER_ARRAY // (s * s)
            arch = ArchSpec(rows=s, cols=s, subarrays_per_array=subs,
                            arrays_per_mat=4, mats_per_bank=4,
                            banks=0).with_target(target)
            prog = compile_fn(hdc_kernel, [(n_queries, dim),
                                           (n_classes, dim)], arch,
                              value_bits=1, unroll_limit=0)
            rep = prog.cost_report()
            results[(mode, s)] = rep
            rows.append({"mode": mode, "subarray": f"{s}x{s}",
                         "subarrays/array": subs,
                         "latency_us": rep.latency_us,
                         "energy_uj": rep.energy_uj,
                         "power_w": rep.power_w})
    print(table(rows))

    base_e = [results[("iso-base", s)].energy_fj for s in (16, 32, 64, 128, 256)]
    spread = max(base_e) / min(base_e)
    print(f"\niso-base energy spread across sizes: {spread:.2f}x "
          f"(paper: nearly constant)")
    assert spread < 2.0

    base_t = [results[("iso-base", s)].latency_ns for s in (16, 32, 64, 128, 256)]
    assert base_t[-1] > base_t[0], "exec time grows with column count"
    assert base_t[-1] / base_t[0] < 6, "…but stays within a moderate band"

    imp = np.mean([results[("iso-base", s)].energy_fj
                   / results[("cam-density", s)].energy_fj
                   for s in (16, 32, 64)])
    print(f"cam-density energy improvement @16..64: {imp:.2f}x "
          f"(paper ~1.75x avg)")
    assert imp > 1.2

    for s in (16, 32, 64, 128, 256):
        assert results[("cam-power+density", s)].power_w < \
            results[("iso-base", s)].power_w

    save_json("fig9_isocapacity", rows)
    return rows


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
