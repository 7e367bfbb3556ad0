from .ops import (bsr_spmm, bsr_spmv, bsr_spmv_scheduled,  # noqa: F401
                  ell_device_arrays, prepare, prepare_sell,
                  prepare_with_schedule, sell_device_arrays)
from .ref import (ref_bsr_spmm, ref_bsr_spmm_sell, ref_bsr_spmv,  # noqa: F401
                  ref_bsr_spmv_sell, ref_dia_spmv)
