from .collectives import (bcast_from, reduce_sum, reduce_max, maxloc,
                          ring_shift, tree_reduce_pairwise)
from .panel import dist_panel_getrf
from .summa import gemm_summa
