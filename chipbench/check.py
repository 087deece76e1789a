"""The comparison that decides ``correct``: what the program produced in the
iterations the reference follows, against the reference.

Each number is a gap; smaller is closer.  A cell's limits are in
``limits/<cell>.json``; a number without a limit is reported, not judged.
"""

from __future__ import annotations

import math

import numpy as np

# leaves whose reference gradient is under this share of the median leaf's
# move by round-off alone (a key's bias under softmax) and are left out of
# the parameter-change comparison
NULL_GRAD = 1e-3


def _max_gap(prog, ref, key, scale=False):
    worst = 0.0
    for p, r in zip(prog, ref):
        gap = np.max(np.abs(np.asarray(p[key], np.float64)
                            - np.asarray(r, np.float64)))
        if scale:
            gap /= max(float(np.sqrt(np.mean(np.square(r)))), 1e-30)
        worst = max(worst, float(gap))
    return worst


def worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    """Largest | |prog leaf| - |ref leaf| | over the leaves, each against
    the larger of its reference norm and the median leaf's."""
    med = float(np.median(list(ref.values())))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if keep is None or k in keep]
    return max(gaps)


def numbers(prog: dict, ref: dict) -> dict:
    """prog: ``iterations`` (records of ``cell.record``), ``m`` and
    ``change`` (``cell.opt_norms``).  ref: ``reference.follow``'s output."""
    its = prog["iterations"][:len(ref["logp"])]
    out = {
        "rollout_logp": _max_gap(its, ref["logp"], "logp"),
        "ref_logp": _max_gap(its, ref["ref_logp"], "ref_logp"),
        "values": _max_gap(its, ref["values"], "values", scale=True),
        "rewards": _max_gap([{"r": i["rewards"]} for i in its], ref["rewards"],
                            "r", scale=True),
        "actor_loss": max(abs(i["actor_loss"] - r)
                          for i, r in zip(its, ref["actor_loss"])),
        "critic_loss": max(abs(i["critic_loss"] - r) / max(abs(r), 1e-30)
                           for i, r in zip(its, ref["critic_loss"])),
    }
    for model in ("actor", "critic"):
        m_ref = ref[f"{model}_m"]
        med = float(np.median(list(m_ref.values())))
        moved = {k for k, v in m_ref.items() if v >= NULL_GRAD * med}
        out[f"{model}_grad"] = worst_leaf(prog["m"][model], m_ref)
        out[f"{model}_change"] = worst_leaf(prog["change"][model],
                                            ref[f"{model}_change"], moved)
    return out


def verdict(nums: dict, limits: dict | None) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}).  Without limits nothing is
    proven, so the run is not correct."""
    shown = {}
    ok = limits is not None
    for name, value in nums.items():
        lim = (limits or {}).get(name, {}).get("limit")
        shown[name] = {"value": value, "limit": lim}
        if not math.isfinite(value):
            ok = False
        elif lim is not None and value > lim:
            ok = False
    return ok, shown


def as_program(ref: dict) -> dict:
    """A reference's output in the program's place: how the control (the
    reference at a lower precision) and faults planted in the reference
    are compared with the float32 reference."""
    n = len(ref["logp"])
    return {"iterations": [
        {"logp": ref["logp"][t], "ref_logp": ref["ref_logp"][t],
         "values": ref["values"][t], "rewards": ref["rewards"][t],
         "actor_loss": ref["actor_loss"][t],
         "critic_loss": ref["critic_loss"][t]} for t in range(n)],
        "m": {"actor": ref["actor_m"], "critic": ref["critic_m"]},
        "change": {"actor": ref["actor_change"],
                   "critic": ref["critic_change"]}}
