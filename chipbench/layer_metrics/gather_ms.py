"""Operators: device time under the ``gather`` scope per window operation
(see ``_phase``)."""
from chipbench.layer_metrics._phase import read as _read


def read(run):
    return _read(run, "gather")
