#!/usr/bin/env python3
"""``control_on_chip.py`` for a cell whose control the chip cannot hold as
the reference is written:

    chiprun -- python3 chipbench/tests/control_on_chip_remat.py --workload <cell> --seeds 1 2 3

The reference keeps every expert's residuals of its ``lax.map`` for the
backward pass.  At ``trainticket-e200`` (E=200, F=2048) the float32
reference still fits one v5e (14.6 GB compiled, beside its 1.55 GB start
copy), but the fp8 control, whose rounding keeps two more ``[F, 3H]``
arrays an expert and direction, does not (16.48 of the chip's 15.75 GiB).
Here the function the reference maps over the experts is wrapped in
``jax.checkpoint`` for the whole process, so each expert is computed again
in the backward pass instead of kept (7.8 GB compiled): the same
operations on the same values in the same order, and the same numbers to
float32's rounding (``test_control_remat.py`` holds that on the CPU).
Nothing else differs: arguments, output and the cell's limits are
``control_on_chip.py``'s.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def remat_expert_map() -> None:
    """From here on ``jax.lax.map`` rematerialises the function it maps."""
    import jax

    plain = jax.lax.map
    jax.lax.map = lambda f, xs, **kw: plain(jax.checkpoint(f), xs, **kw)


if __name__ == "__main__":
    from chipbench.tests import control_on_chip

    remat_expert_map()
    sys.exit(control_on_chip.main())
