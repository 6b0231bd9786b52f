"""Operators: device time under the ``pack`` scope per window operation
(see ``_phase``)."""
from chipbench.layer_metrics._phase import read as _read


def read(run):
    return _read(run, "pack")
