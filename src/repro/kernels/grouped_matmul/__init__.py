from repro.kernels.grouped_matmul.kernel import gmm, tgmm
from repro.kernels.grouped_matmul.ops import grouped_matmul, tiles
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref

__all__ = ["grouped_matmul", "tiles", "gmm", "tgmm", "grouped_matmul_ref"]
