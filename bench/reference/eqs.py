"""Plain float64 numpy Eqs. 6-10 of TEASQ-Fed: the staleness-weighted fold
of a cache of K local models into the global model."""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def fold(w_global: Dict, cache: List[Tuple[Dict, int, int]], t: int,
         alpha: float, a: float, dtype=np.float64) -> Dict:
    """``cache`` holds ``(w_c, h_c, n_c)``: a local model, the round it
    started from and its sample count.  Every step is rounded to
    ``dtype``."""
    r = lambda x: np.asarray(x, dtype)
    st = r([t - h for _, h, _ in cache])
    n = r([n_c for _, _, n_c in cache])
    wts = r(r(r(st + r(1.0)) ** r(-a)) * n)            # Eqs. 6-7
    wts = r(wts / r(wts.sum(dtype=dtype)))
    a_t = r(r(alpha) * r(r(st.mean(dtype=dtype) + r(1.0)) ** r(-a)))  # Eqs. 8-9
    out = {}
    for name in w_global:
        u = r(0.0)
        for c, (w, _, _) in zip(wts, cache):
            u = r(u + r(c * r(w[name])))
        out[name] = r(r(a_t * u) + r(r(r(1.0) - a_t) * r(w_global[name])))
    return out                                         # Eq. 10


def rel_l2(got: Dict, ref: Dict) -> float:
    num = sum(float(np.sum((np.asarray(got[k], np.float64)
                            - np.asarray(ref[k], np.float64)) ** 2)) for k in ref)
    den = sum(float(np.sum(np.asarray(ref[k], np.float64) ** 2)) for k in ref)
    return float(np.sqrt(num / den))
