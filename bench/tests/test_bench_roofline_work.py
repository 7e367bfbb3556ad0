"""The work a roofline share is measured against: bytes every layout must
move, worked by hand, and blind to how the operand is laid out."""
import numpy as np
import pytest

import bench_helpers  # noqa: F401  (import paths)
from spbench import peaks, work

V5E = peaks.peaks("TPU v5 lite")


def test_spmv_bytes_of_the_64_cubed_stencil():
    # 6,859,000 values + x and y of 262,144 rows, all f32
    assert work.spmm_bytes(6_859_000, 262_144, 262_144) == \
        4 * 6_859_000 + 8 * 262_144 == 29_533_152
    assert work.spmm_flops(6_859_000) == 13_718_000


def test_spmm_bytes_count_each_real_right_hand_side():
    assert work.spmm_bytes(6_859_000, 262_144, 262_144, k=8) == \
        4 * 6_859_000 + 8 * 8 * 262_144
    # a drain of 5 requests: 5 vectors in, 5 out, values read once
    assert work.spmm_bytes(100, 10, 12, k=5) == 4 * 100 + 4 * 5 * 22


def test_roofline_is_bound_by_bytes_on_a_v5e():
    b, f = work.spmm_bytes(6_859_000, 262_144, 262_144), \
        work.spmm_flops(6_859_000)
    least = b / 819e9
    assert work.roofline_pct(b, f, least, V5E) == pytest.approx(100.0)
    assert work.roofline_pct(b, f, 10 * least, V5E) == pytest.approx(10.0)
    assert work.roofline_pct(b, f, 0.0, V5E) is None


def test_a_padded_operand_does_not_raise_the_share():
    """The same stencil built at two block sizes stores different tiles
    (padding), but the bytes counted are the same; the padded layout can
    only take longer, so its share can only be lower."""
    import sys
    import os
    sys.path.insert(0, os.path.join(bench_helpers.BENCH, "generators"))
    import stencil27
    from repro.core.autotune import Schedule
    from repro.core.csr import CSR
    from repro.sparse import SparseTensor
    a = stencil27.build({"nx": 8, "ny": 8, "nz": 8, "diagonal": 26}, 1)
    csr = CSR(a["row_ptrs"], a["col_idxs"].astype(np.uint32), a["vals"],
              a["shape"])
    stored = {bs: SparseTensor.from_csr(csr, Schedule("bsr", bs, 1.0),
                                        shape_bucket=True)
              .arrays["blocks"].size for bs in (32, 128)}
    assert stored[128] > stored[32] > a["vals"].size
    counted = work.spmm_bytes(a["vals"].size, *a["shape"])
    t_fit = counted / 819e9 * 50
    t_pad = t_fit * stored[128] / stored[32]
    flops = work.spmm_flops(a["vals"].size)
    assert work.roofline_pct(counted, flops, t_pad, V5E) < \
        work.roofline_pct(counted, flops, t_fit, V5E) <= 100


def test_an_unknown_device_has_no_peaks():
    with pytest.raises(KeyError, match="cpu"):
        peaks.peaks("cpu")
