"""The candidate kernel's share of its roofline: the least time the chip
could take for the work an exact join must do (bench.roofline), over the
kernel's device time, for the micro-batches of the traced window.  A ring
split over ``shards`` chips splits that work: each chip's share of the
least time, over the kernel's mean device time per chip."""

import sys

import numpy as np

from bench import gen, roofline


def read(r):
    s = r.op_s("sssj_candidates")
    if s is None or not r.batches:
        return None
    nbytes, flops = roofline.cand_work(
        np.asarray(r.tenant), gen.horizons(r.cfg), r.batches, r.cfg["d"])
    t_min, bound = roofline.least_time(nbytes, flops,
                                       roofline.peaks(r.device_kind))
    print(f"cand_roofline: bound by {bound}; least {t_min.sum():.6f} s "
          f"over {len(r.batches)} micro-batches", file=sys.stderr)
    return 100.0 * float(t_min.sum()) / r.cfg.get("shards", 1) / s
