"""Share of the traced window in which no operation ran on the device and
the host was inside the engine's ``serve.gather`` or
``serve.gather.deferred`` span, the device-to-host copy under their
``serve.gather.fetch`` included (%, ``bench/attribution.py``)."""
import attribution


def read(ctx):
    att = attribution.of(ctx)
    if att is None or att["host"] is None:
        return None
    host = att["host"]
    if host["gather_idle_s"] is None:
        return None
    return 100.0 * host["gather_idle_s"] / host["window_s"]
