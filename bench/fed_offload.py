"""Federated cells whose frozen base the chip cannot hold twice: the ``fed``
driver, except that after the window the program's base moves to the host
before the plain reference follows the first rounds.

``ref_fed.run`` casts the base it is given to float32 on the device.  Beside
the program's own bfloat16 copy that is three times the base (5.3 + 10.6 GB
for ``kimi_k2``), more than one chip holds once the reference's own
activations are added.  Handed the host's copy, the reference's float32 base
is the only one on the chip.  The window, its trace and the peak memory are
read before, as in ``fed``; whatever ``fed._checks`` is at the call (the
reference rounds, or ``control.py``'s readings) runs on the host's copy.
"""

from __future__ import annotations

import jax

import fed


def run(ctx) -> dict:
    inner = fed._checks

    def checks(ctx, spec, traffic, data, seed, base, *rest):
        host = jax.device_get(base)
        for leaf in jax.tree.leaves(base):
            leaf.delete()
        return inner(ctx, spec, traffic, data, seed, host, *rest)

    fed._checks = checks
    try:
        return fed.run(ctx)
    finally:
        fed._checks = inner
