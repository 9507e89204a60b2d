"""``grid_pipeline`` (the anti-diagonal wavefront kernel of ``kernel_grid``)
as a share of its roofline, in percent. Sums the custom calls named after
``grid_pipeline_pallas`` and ``grid_pipeline_pallas_with_args``."""
from roofline import share

ROUTES = ("kernel_grid",)
EVENTS = (r"grid_pipeline_pallas",)


def read(run):
    return share(run, ROUTES, EVENTS)
