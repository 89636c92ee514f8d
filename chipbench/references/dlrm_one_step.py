"""DLRM by minibatch SGD where ONE step is held tightly: the plain reference
``chipbench/references/dlrm.py``, equation for equation (it is imported, not
copied), for a deployment whose SECOND step is ill-conditioned between two
float32 systems.

At the source's learning rate 1.0 (``dlrm-criteo-40m-ps4.json``) the dense
weights two correct float32 systems hold after one step differ within their
allowance, and the next batch's pre-activations, computed from those
weights, differ by hundreds of roundings of the magnitudes they were summed
from (340-615 on the v5e at ``Precision.HIGHEST``: the configuration's
``reference.why``), far past the ``relu_ulps`` within which
``references/dlrm.py`` marks a unit ON its ReLU's corner by ITS OWN weights.
An example with such a unit moves its rows by another gradient altogether,
and no summation error explains it.  So:

- the FIRST batch is ``references/dlrm.py``'s, bit for bit: its rows, its
  ``moved``, its corners.  The cell checks one batch, and this is then the
  whole of the check;
- LATER batches (the benchmark's tests hand every reference three) go through
  the same equations, so the rows are what the plain reference gives, and
  are NOT HELD: every element a later batch can move (the rows it names,
  the dense net whole) is allowed anything, as ``references/dlrm_dcnv2.py``
  allows a batch after Adagrad's first step.  (Allowing each element what
  the reference itself moves it by later is not enough: a unit the
  reference has off and the system has on gives its example's rows a
  gradient the reference's addends say nothing of; read 299 x on the CPU.)
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references import dlrm as plain

touched = plain.touched
unpack = plain.unpack


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    first, moved = plain.apply(cfg, rows, ids, batches[:1])
    if len(batches) == 1:
        return first, moved
    after, _ = plain.apply(cfg, rows, ids, batches)
    named, n = ids["embedding"], ids["embedding"].size
    later = np.searchsorted(named, np.concatenate(
        [b["ids"].reshape(-1) for b in batches[1:]]))
    allowed = {}
    for name, first_moved in moved.items():
        free = first_moved.copy()
        free[later] = np.inf  # the rows a later batch names ...
        # (the padding repeats the largest id: every repeat shows its row)
        free[:n] = free[:n][np.searchsorted(named, named)]
        free[n:] = np.inf  # ... and the dense net, which every batch moves
        allowed[name] = free
    return after, allowed
