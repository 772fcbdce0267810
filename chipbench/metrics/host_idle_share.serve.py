"""Share of the traced window in which the device idled while the host
was inside the scheduler or the KV cache (``serve.*`` and ``kv.*`` spans;
not ``serve.on_step``, the caller's hook)."""
import harness


def read(rec):
    return harness.load_module("metrics", "_host_idle").share(
        rec, "serve", ("serve.", "kv."), excluded=("serve.on_step",))
