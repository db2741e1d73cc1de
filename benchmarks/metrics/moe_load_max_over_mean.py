"""How uneven the routed load on this chip's experts is: the largest held
group over the mean held group in the worst expert layer of a step
(``moe_load_max_over_mean`` in ``fit()``'s records), median over the
window's records. 1 is even."""

UNIT = "x"

import statistics


def read(run):
    values = [m["moe_load_max_over_mean"] for _, _, m in run["records"]
              if "moe_load_max_over_mean" in m]
    return statistics.median(values) if values else None
