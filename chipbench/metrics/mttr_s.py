"""From the event (the ``inject_fail_stop`` call) to the end of the first
step after recovery, which ``train_step`` syncs with its ``device_get``."""


def read(ctx):
    m = ctx.window["marks"]
    if "event" not in m or "first_step_after_event" not in m:
        return None
    return m["first_step_after_event"] - m["event"]
