#!/usr/bin/env python3
"""``control_on_chip.py`` for a cell of the ``train_mesh`` runner:

    chiprun -- python3 chipbench/tests/control_on_chip_mesh.py --workload tenk-train-dp4 --seeds 1 2 3

The control of `correct` is the reference put in the program's place, and
the reference has no mesh: for such a cell it is the ``train`` runner's
control on the cell's own configuration (the GLOBAL batch) and mix, on one
chip.  ``control_on_chip.main`` looks its control up by the mix's runner
and knows ``train`` only, so the mix is handed to it under that name.  At
the global batch of 128 the fp8 control does not fit one v5e as the
reference is written (17.6 GB compiled for a described chip), so the map
over the experts is rematerialised as ``control_on_chip_remat.py`` does
(the same numbers to float32's rounding: ``test_control_remat.py``).
Arguments, output and the cell's limits are ``control_on_chip.py``'s.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def mesh_cells_as_train() -> None:
    """From here on ``run.load_cell`` names a ``train_mesh`` mix's runner
    ``train``."""
    from chipbench import run

    plain = run.load_cell

    def load_cell(workload: str) -> dict:
        loaded = plain(workload)
        if loaded["mix"]["runner"] == "train_mesh":
            loaded["mix"] = {**loaded["mix"], "runner": "train"}
        return loaded

    run.load_cell = load_cell


if __name__ == "__main__":
    from chipbench.tests import control_on_chip, control_on_chip_remat

    mesh_cells_as_train()
    control_on_chip_remat.remat_expert_map()
    sys.exit(control_on_chip.main())
