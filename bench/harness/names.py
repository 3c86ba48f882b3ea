"""Names the program gives the work the per-layer metrics look for in a
device trace.  They are the program's own: the jitted functions of
``ParallelADMMTrainer`` (an XLA module is named ``jit_<function>``), the
``name=`` of its Pallas aggregation kernels, and XLA's name for the
``ppermute`` exchange."""
from __future__ import annotations

import re

EVAL_MODULE = re.compile(r"jit_(metrics|lagrangian)\b")
AGG_KERNEL = re.compile(r"community_spmm")
EXCHANGE_OP = re.compile(r"collective-permute")


def matcher(pattern: re.Pattern):
    return lambda name: pattern.search(name) is not None
