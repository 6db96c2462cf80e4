from .collectives import (bcast_from, reduce_sum, reduce_max, maxloc,
                          ring_shift, tree_reduce_pairwise)
from .summa import gemm_summa
