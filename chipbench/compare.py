"""The numbers that decide ``correct``, each against a limit of its own.

Training (the program's first steps against the reference's):
  loss_gap_step<i>   |loss - reference| / |reference| of step i
  grad_norm_gap      the first gradient as the optimizer got it, by leaf:
                     the gap between the program's norm and the
                     reference's, over the reference's norm of that leaf or
                     of the median leaf, whichever is larger; worst leaf
  change_norm_gap    the same for the parameters' change after the last
                     followed step, over the leaves that count: a leaf
                     whose reference gradient is under a thousandth of the
                     median leaf's moves under Adam by round-off alone and
                     is left out, by that rule and not by name
  grad_diff_gap      the norm of the DIFFERENCE between the program's first
                     gradient and the reference's, by leaf, over the same
                     measure; worst leaf; taken over every n-th row of
                     every leaf (the mix's ``direction_rows``), which is what
                     both sides bring to the host.  A norm is blind to direction and
                     to unbiased rounding noise; this is not
  change_diff_gap    the same for the parameters' change (a flipped update
                     reads 2, a state left unchanged 1)

Serving (a sample of the requests the window finished):
  served_token_gap   the widest gap by which a served token's logit lies
                     below the reference's best at its position, over the
                     RMS of the reference's logits
  short_answers      finished requests whose answer has not the length
                     asked for (exact: the limit is 0)

A number that is missing (NaN, a leaf the program does not have) fails.
"""
import statistics

import numpy as np

NEGLIGIBLE_GRADIENT = 1e-3      # of the median leaf's gradient norm


def _worst_leaf(gaps, want, leaves):
    """``gaps``: leaf -> how far the program is from the reference (a gap
    of norms, or the norm of a difference); ``want``: leaf -> the
    reference's norm.  The worst leaf's gap over the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    floor = statistics.median(want[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        if k not in gaps:
            return float("nan"), k
        gap = gaps[k] / max(want[k], floor)
        if not gap <= worst:            # NaN counts as worst
            worst, where = gap, f"{k}: {gaps[k]:.6g} off {want[k]:.6g}"
    return worst, where


def _norm_gaps(got, want):
    return {k: abs(got[k] - want[k]) for k in want if k in got}


def _diff_norms(got, want, scale=1.0):
    """(leaf -> ||scale * got - want||, leaf -> ||want||) over the rows
    that both sides kept, on the host, one leaf at a time."""
    gaps, norms = {}, {}
    for k, w in want.items():
        w = np.asarray(w, np.float32).ravel()
        norms[k] = float(np.linalg.norm(w))
        if k in got:
            g = np.asarray(got[k], np.float32).ravel() * np.float32(scale)
            gaps[k] = float(np.linalg.norm(g - w))
    return gaps, norms


def train_numbers(got, want):
    """name -> (value, detail) for one run against the reference."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        out[f"loss_gap_step{i}"] = (abs(a - b) / abs(b), f"{a:.6f} vs {b:.6f}")
    leaves = list(want["grad_norms"])
    raw = want["raw_grad_norms"]
    floor = NEGLIGIBLE_GRADIENT * statistics.median(raw.values())
    counted = [k for k in leaves if raw[k] >= floor]
    out["grad_norm_gap"] = _worst_leaf(
        _norm_gaps(got["grad_norms"], want["grad_norms"]),
        want["grad_norms"], leaves)
    out["change_norm_gap"] = _worst_leaf(
        _norm_gaps(got["change_norms"], want["change_norms"]),
        want["change_norms"], counted)
    out["grad_diff_gap"] = _worst_leaf(
        *_diff_norms(got["grad_leaves"], want["grad_leaves"],
                     got.get("grad_scale", 1.0)), leaves)
    out["change_diff_gap"] = _worst_leaf(
        *_diff_norms(got["change_leaves"], want["change_leaves"]), counted)
    return out


def checks(values, limits):
    """name -> value, each beside its limit.  A number the limits file
    does not name is an error, not a pass; one it names with ``null`` has
    no upper reading (nothing that should fail reads far enough above
    sound runs), so it is read and printed by control.py but not
    compared: it could only fail sound runs."""
    missing = sorted(set(values) - set(limits))
    if missing:
        raise SystemExit(f"no limit is set for {missing}")
    return [(name, value, limits[name]) for name, value in values.items()
            if limits[name] is not None]
