"""Fig. 8 — design-space exploration: subarray size x optimization mode.

HDC / MNIST-8k on square subarrays R = C in {16, 32, 64, 128, 256} under
the four C4CAM configurations (cam-base, cam-power, cam-density,
cam-power+density); 4 mats/bank, 4 arrays/mat, 8 subarrays/array, banks as
needed.  Reports latency, energy, and power, and checks the paper's
quantitative anchors:

* cam-power @16x16 uses ~0.57x base power; largest size ~20%;
* cam-power @32x32 latency ~2x base, rising to ~4.86x at 256x256;
* cam-density energy ~0.6x base for small arrays, crossing to >1x at
  128/256 (1.4x / 5.1x in the paper);
* cam-power+density @16x16 ~23.4% base power, largest ~4.2%, with up to
  ~121x slower execution.
"""

from __future__ import annotations

from repro.core import ArchSpec, OptimizationTarget, compile_fn

from .common import banner, save_json, table

MODES = [("cam-base", OptimizationTarget.LATENCY),
         ("cam-power", OptimizationTarget.POWER),
         ("cam-density", OptimizationTarget.DENSITY),
         ("cam-power+density", OptimizationTarget.POWER_DENSITY)]

SIZES = (16, 32, 64, 128, 256)


def hdc_kernel(inp, weight):
    others = weight.transpose(-2, -1)
    mm = inp.matmul(others)
    return mm.topk(1, largest=False)


def run(n_queries: int = 10_000, dim: int = 8192, n_classes: int = 10):
    banner("Fig. 8 — DSE: subarray size x optimization mode (HDC/MNIST-8k)")
    results = {}
    rows = []
    for mode, target in MODES:
        for s in SIZES:
            arch = ArchSpec(rows=s, cols=s).with_target(target)
            prog = compile_fn(hdc_kernel, [(n_queries, dim),
                                           (n_classes, dim)], arch,
                              value_bits=1, unroll_limit=0)
            rep = prog.cost_report()
            results[(mode, s)] = rep
            rows.append({"mode": mode, "subarray": f"{s}x{s}",
                         "latency_us": rep.latency_us,
                         "energy_uj": rep.energy_uj,
                         "power_w": rep.power_w})
    print(table(rows))

    def ratio(mode, s, field):
        base = getattr(results[("cam-base", s)], field)
        return getattr(results[(mode, s)], field) / base

    checks = {
        "power@16 power ratio (paper ~0.57)": ratio("cam-power", 16, "power_w"),
        "power@256 power ratio (paper ~0.20)": ratio("cam-power", 256, "power_w"),
        "power@32 latency ratio (paper ~2x)": ratio("cam-power", 32, "latency_ns"),
        "power@256 latency ratio (paper ~4.86x)": ratio("cam-power", 256, "latency_ns"),
        "density@16..64 energy ratio (paper ~0.6)":
            sum(ratio("cam-density", s, "energy_fj") for s in (16, 32, 64)) / 3,
        "density@128 energy ratio (paper ~1.4)": ratio("cam-density", 128, "energy_fj"),
        "density@256 energy ratio (paper ~5.1)": ratio("cam-density", 256, "energy_fj"),
        "power+density@16 power ratio (paper ~0.234)":
            ratio("cam-power+density", 16, "power_w"),
        "power+density@256 power ratio (paper ~0.042)":
            ratio("cam-power+density", 256, "power_w"),
        "power+density@256 latency ratio (paper ~121x)":
            ratio("cam-power+density", 256, "latency_ns"),
    }
    print()
    for k, v in checks.items():
        print(f"  {k}: {v:.3f}")

    # direction-of-effect assertions (the reproduction claims)
    assert checks["power@16 power ratio (paper ~0.57)"] < 1.0
    assert checks["power@256 power ratio (paper ~0.20)"] < \
        checks["power@16 power ratio (paper ~0.57)"]
    assert checks["power@32 latency ratio (paper ~2x)"] > 1.5
    assert checks["density@16..64 energy ratio (paper ~0.6)"] < 1.0
    assert checks["density@256 energy ratio (paper ~5.1)"] > 1.0
    assert checks["power+density@256 power ratio (paper ~0.042)"] < 0.1
    assert checks["power+density@256 latency ratio (paper ~121x)"] > 20

    save_json("fig8_dse", {"rows": rows,
                           "checks": {k: float(v) for k, v in checks.items()}})
    return rows


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
