from repro.blas import level1, level2, level3
from repro.blas.level1 import asum, axpy, dot, iamax, nrm2, rot, scal
from repro.blas.level2 import gemv, ger, trsv
from repro.blas.level3 import gemm, syrk, trsm
from repro.blas import distributed
from repro.blas.distributed import make_blas_mesh, mesh_key, pdgemm, pdtrsm
