"""Sort operator: its share of the roofline (see ``_roofline``)."""
from chipbench.layer_metrics._roofline import read  # noqa: F401
