"""Share of the traced window in which the device idled while the host
was inside ``quantize_tree`` planning, stacking, dispatching or wrapping
(``quantize.*`` spans; not ``quantize.sync``, where the host waits for the
device by design)."""
import harness


def read(rec):
    return harness.load_module("metrics", "_host_idle").share(
        rec, "quantize", ("quantize.",), excluded=("quantize.sync",))
