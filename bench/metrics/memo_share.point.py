"""Share of the window's requests that took no lane of their own (%): the
LRU, the component memo or a twin already queued or in flight answered
them. A request took a lane when its submission grew the engine's pending
queue (``BFSServeEngine.stream_status``)."""


def read(ctx):
    if not ctx["sent"]:
        return None
    return 100.0 * ctx["no_lane"] / ctx["sent"]
