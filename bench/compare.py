"""The output check: the program's first rounds against the plain reference.

A federated round is the training step here: the server's state is the
global parameter set and its "gradient" is the round's update.  Numbers
compared (each has its own limit in ``limits/<cell>.json``):

* ``loss_gap``: over rounds 1-3, the largest relative gap between the
  round's mean local loss as the program recorded it and as the
  reference computes it.
* ``update_gap``: the round-1 update ``p1 - p0``, by the worst leaf: the
  gap between the program's norm of the leaf's update and the
  reference's, over the larger of the reference's norm for that leaf and
  for the median leaf.
* ``change_gap``: the same for the change after three rounds, ``p3 - p0``.
* ``client_loss_gap``: over every participant of round 1, the median
  relative gap between the participant's last-epoch mean loss as the
  program computed it and as the reference does.  Round 1 starts from the
  initial weights and most hospitals run a handful of local steps, so this
  is read before AdamW's sign-sensitive steps have carried rounding
  differences far; it is the number that separates a lower-precision step
  where round 1 trains many hospitals.
* ``slice_loss_gap``, ``slice_update_gap``: the same two for a round the
  harness runs from the initial weights over a contiguous run of resident
  clients, which takes the engine's static-slice path (only where the
  traffic samples part of the federation).
* ``participants_mismatch``: rounds of the whole run (set-up and window)
  whose participant ids differ from the reference's replay of
  recruitment and selection; the limit is 0.

``p0`` is the reference's own initialisation from the seed, so a program
that started from other weights shows in both gaps.  Leaves whose
reference update is under a thousandth of the median leaf's are left out
of both gaps: AdamW moves those by round-off alone (none of the GRU's
leaves is such a leaf at the paper's sizes, but the rule is kept general).
"""

from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3


def _norms(after: dict, before: dict) -> dict[str, float]:
    return {
        k: float(np.linalg.norm(np.asarray(after[k], np.float64) - np.asarray(before[k], np.float64)))
        for k in before
    }


def _worst_gap(program: dict[str, float], reference: dict[str, float], keep: list[str]) -> float:
    median = float(np.median([reference[k] for k in keep]))
    return max(abs(program[k] - reference[k]) / max(reference[k], median) for k in keep)


def _median_gap(program: list[float], reference: list[float]) -> float:
    if len(program) != len(reference):
        return math.inf
    return float(np.median([abs(a - b) / abs(b) if math.isfinite(a) else math.inf for a, b in zip(program, reference)]))


def readings(program: dict, reference: dict, replay: list[list[int]]) -> dict[str, float]:
    """``program``: losses, client_losses (per round, per participant), p1,
    p3 (flat leaf dicts), participants per round, and ``extra`` (params and
    client_losses of the slice-path round) where the run made one.

    ``reference``: the output of a reference's ``train`` (p0, params,
    losses, client_losses, extra); ``replay``: the reference's participants
    for every round.
    """
    p0 = reference["p0"]
    if set(program["p1"]) != set(p0):
        raise ValueError(f"leaf sets differ: {sorted(program['p1'])} vs {sorted(p0)}")
    ref_u = _norms(reference["params"][0], p0)
    median_u = float(np.median(list(ref_u.values())))
    keep = [k for k in sorted(p0) if ref_u[k] >= NEGLIGIBLE * median_u]
    n = len(reference["losses"])
    loss_gap = max(
        abs(a - b) / abs(b) if math.isfinite(a) else math.inf
        for a, b in zip(program["losses"][:n], reference["losses"])
    )
    out = {
        "loss_gap": loss_gap,
        "update_gap": _worst_gap(_norms(program["p1"], p0), ref_u, keep),
        "change_gap": _worst_gap(_norms(program["p3"], p0), _norms(reference["params"][n - 1], p0), keep),
        "participants_mismatch": float(
            sum(1 for got, want in zip(program["participants"], replay) if got != want)
            + abs(len(program["participants"]) - len(replay))
        ),
        "client_loss_gap": _median_gap(program["client_losses"][0], reference["client_losses"][0]),
    }
    if "extra" in reference:
        got, want = program.get("extra"), reference["extra"]
        if got is None:
            out["slice_loss_gap"] = out["slice_update_gap"] = math.inf
        else:
            out["slice_loss_gap"] = _median_gap(got["client_losses"], want["client_losses"])
            out["slice_update_gap"] = _worst_gap(_norms(got["params"], p0), _norms(want["params"], p0), keep)
    return out


def as_program(reference: dict, participants: list[list[int]]) -> dict:
    """A reference run's outputs in the place of the program's."""
    out = {
        "losses": reference["losses"],
        "client_losses": reference["client_losses"],
        "p1": reference["params"][0],
        "p3": reference["params"][-1],
        "participants": participants,
    }
    if "extra" in reference:
        out["extra"] = reference["extra"]
    return out


def judge(values: dict[str, float], limits: dict) -> tuple[dict, bool]:
    """Each number the cell's limits file names, beside its limit.

    Correct when every one is within its limit; a number that is not
    finite, or that has no limit set, fails.  Readings the file does not
    name are not compared.
    """
    checks = {}
    ok = bool(limits)
    for name, entry in limits.items():
        value, limit = values[name], entry.get("limit")
        checks[name] = {"value": value, "limit": limit}
        if limit is None or not math.isfinite(value) or value > limit:
            ok = False
    return checks, ok
