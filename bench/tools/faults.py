"""Faults planted under the timed path, for showing that ``correct``
comes out false. Each patches the program's own functions for the length
of a ``with`` block; the session has to be built (and its step traced)
inside it.

* ``unchanged``  - the step returns its state unchanged;
* ``half_batch`` - each client's gradient from the first half of its rows,
  the mean taken over those;
* ``msg_altered`` - every aggregated EF message doubled where the carrier
  produces it.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "msg_altered")


@contextlib.contextmanager
def planted(name: str):
    import jax

    from bench import program
    program.import_program()
    from repro.core import carriers
    from repro.core import distributed as dist

    saved = []

    def patch(obj, attr, value):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)

    if name == "unchanged":
        make = dist.make_train_step

        def make_unchanged(*a, **kw):
            step = make(*a, **kw)

            def unchanged(params, opt_state, ef_state, batch, rng, t):
                m = step(params, opt_state, ef_state, batch, rng, t)[3]
                return params, opt_state, ef_state, m
            return unchanged
        patch(dist, "make_train_step", make_unchanged)
    elif name == "half_batch":
        grads = dist.per_client_value_and_grad

        def half(loss_fn, params, batch, dp):
            def cut(x):
                rows = x.reshape(dp, x.shape[0] // dp, *x.shape[1:])
                rows = rows[:, :max(1, rows.shape[1] // 2)]
                return rows.reshape(-1, *x.shape[1:])
            return grads(loss_fn, params,
                         jax.tree_util.tree_map(cut, batch), dp)
        patch(dist, "per_client_value_and_grad", half)
    elif name == "msg_altered":
        cls = carriers.FusedQuantCarrier
        inner = cls.fused_wire_round

        def doubled(self, *a, **kw):
            msg, state = inner(self, *a, **kw)
            return jax.tree_util.tree_map(lambda m: 2 * m, msg), state
        patch(cls, "fused_wire_round", doubled)
    else:
        raise KeyError(name)
    try:
        yield
    finally:
        for obj, attr, value in reversed(saved):
            setattr(obj, attr, value)
