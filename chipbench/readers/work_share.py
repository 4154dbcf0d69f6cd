"""The work the window's completed units REQUIRE, from shapes, over
the time the window took, as a share of a published peak of the chips
used. ``work`` names ``<module>.<function>`` under ``chipbench/work/``
giving one unit's operations or bytes from the configuration."""

import importlib

from chipbench import peaks


def read(ctx, work, peak):
    module, func = work.split(".")
    per_unit = getattr(importlib.import_module("chipbench.work." + module),
                       func)(ctx["config"])
    top = peaks.peak(ctx["device_kind"], peak, ctx["peaks"])
    if not ctx["units_done"]:
        return None
    return (100.0 * ctx["units_done"] * per_unit / ctx["elapsed"]
            / (top * ctx["n_devices"]))
