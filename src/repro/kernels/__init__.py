"""Pallas TPU kernels for the compute hot-spots.

* `cam_search`      — the paper's primitive: fused distance + block top-k
                      (hamming / dot / L2), `ops.py` wrappers, `ref.py`
                      pure-jnp oracles.
* `packing`         — uint32 bit-lane packing + popcount for the packed
                      binary/ternary (TCAM wildcard) fast path:
                      `hamming = popcount(q ^ p)`, ternary
                      `popcount((q ^ p) & care)`.
* `acam`            — analog-CAM range search: fused interval match
                      (`lo <= q <= hi` per cell, wildcard = full range)
                      and in-kernel thresholded distance match (the
                      paper's TH sensing mode).
* `hdc_encode`      — fused HDC hypervector encoding (bind + majority
                      bundle via the one-hot matmul decomposition);
                      oracles `ref.hdc_bind/hdc_bundle/hdc_permute/
                      hdc_encode`.
* `flash_attention` — online-softmax attention forward (the LM framework's
                      hot spot; §Perf cell B's TPU answer).

All kernels are written for TPU (pl.pallas_call + BlockSpec VMEM tiling),
validated on CPU in interpret mode against the oracles, and compiled for
a described TPU v5e chip by ``tests/test_tpu_compile.py``.
"""
