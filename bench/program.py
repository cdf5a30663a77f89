"""What the entries take from the program under test besides its entry
points: its device parameters, checked against the configuration."""
from __future__ import annotations


def afmtj_params(cfg: dict):
    """The program's ``DeviceParams`` built from the configuration's
    numbers, which must equal the program's own AFMTJ set: the cell then
    runs the program as its configuration states."""
    from repro.core.params import AFMTJ_PARAMS, DeviceParams

    p = DeviceParams(**cfg["device"])
    if p != AFMTJ_PARAMS:
        raise ValueError("the configuration's device differs from the "
                         "program's AFMTJ parameters")
    return p
