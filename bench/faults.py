"""Faults planted in the program under test, to show that the check
catches them.  Each takes a built ``Federation`` and breaks its timed path
underneath, the way a wrong optimisation would.

- ``state_unchanged``: the server step returns its state unchanged.
- ``half_cohort``: half of the cohort's updates are left out, and the mean
  is taken over the rest.
- ``altered_update``: one client's update is altered where the trainer
  produces it (its sign flipped).

A one-chip cell has no exchange between chips to leave out.
"""
from __future__ import annotations


def state_unchanged(fed) -> None:
    fed.ctx.server_apply = lambda state, mean_delta: state._replace(round=state.round + 1)


def half_cohort(fed) -> None:
    aggregate = fed.ctx.aggregate

    def first_half(rows, weights, key, clients=None):
        h = len(weights) // 2
        return aggregate(rows[:h], weights[:h], key, clients=None if clients is None else clients[:h])

    fed.ctx.aggregate = first_half


def altered_update(fed) -> None:
    trainer = fed.ctx.cohort_trainer

    def flipped(*args):
        res = trainer(*args)
        return res._replace(rows=res.rows.at[0].multiply(-1.0))

    fed.ctx.cohort_trainer = flipped


FAULTS = {f.__name__: f for f in (state_unchanged, half_cohort, altered_update)}
