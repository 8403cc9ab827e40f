"""§IV-B — end-to-end comparison against the GPU baseline.

The paper measures PyTorch int32 HDC/MNIST on a Quadro RTX 6000 and
reports the CIM system (CAM banks + host periphery, config of [22]) to be
48x faster and 46.8x more energy-efficient, "which is nearly the same
since CAMs contribute minimally to the overall energy consumption in
their CIM system".

No GPU exists in this container, so the RTX 6000 is modelled analytically
(datasheet roofline x measured-efficiency factor; `repro.camsim.gpu`).
The efficiency factor is CALIBRATED so the modelled time ratio lands at
the paper's 48x — reported explicitly below, so what this benchmark
demonstrates is the *energy-ratio consistency* (46.8x follows from 48x +
the CIM system model, not from an independent fit) and the end-to-end
pipeline: same TorchScript-like kernel, two backends.
"""

from __future__ import annotations

from repro.camsim import CIM_SYSTEM, CostModel, QUADRO_RTX_6000
from repro.core import compile_fn, kazemi_arch

from .common import banner, save_json


def hdc_kernel(inp, weight):
    others = weight.transpose(-2, -1)
    mm = inp.matmul(others)
    return mm.topk(1, largest=False)


def run(n_queries: int = 10_000, dim: int = 8192, n_classes: int = 10):
    banner("GPU comparison — HDC/MNIST-8k, CIM system [22] vs RTX 6000")
    arch = kazemi_arch(64)
    prog = compile_fn(hdc_kernel, [(n_queries, dim), (n_classes, dim)],
                      arch, value_bits=1, unroll_limit=0)
    rep = prog.cost_report()

    cam_time_s = CIM_SYSTEM.system_time_s(rep.latency_ns, n_queries)
    cam_energy_j = CIM_SYSTEM.system_energy_j(rep.energy_fj, n_queries)

    gpu = QUADRO_RTX_6000.similarity_workload(n_queries, n_classes, dim,
                                              bytes_per_el=4)

    t_ratio = gpu["time_s"] / cam_time_s
    e_ratio = gpu["energy_j"] / cam_energy_j
    print(f"CAM system : {cam_time_s * 1e6:.1f} us, "
          f"{cam_energy_j * 1e6:.2f} uJ")
    print(f"GPU model  : {gpu['time_s'] * 1e6:.1f} us, "
          f"{gpu['energy_j'] * 1e6:.1f} uJ "
          f"(efficiency factor {QUADRO_RTX_6000.efficiency}, calibrated)")
    print(f"execution-time improvement : {t_ratio:.1f}x (paper 48x)")
    print(f"energy improvement         : {e_ratio:.1f}x (paper 46.8x)")

    assert 20 < t_ratio < 120, "time ratio must land in the paper's regime"
    assert 0.5 < (e_ratio / t_ratio) < 2.0, \
        "energy ratio tracks time ratio (CAM energy is a minor term)"

    out = {"cam_time_us": cam_time_s * 1e6,
           "cam_energy_uj": cam_energy_j * 1e6,
           "gpu_time_us": gpu["time_s"] * 1e6,
           "gpu_energy_uj": gpu["energy_j"] * 1e6,
           "time_ratio": t_ratio, "energy_ratio": e_ratio,
           "calibrated_efficiency": QUADRO_RTX_6000.efficiency}
    save_json("gpu_comparison", out)
    return out


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
