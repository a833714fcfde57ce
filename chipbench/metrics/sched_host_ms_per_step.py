"""Host time in the scheduler's ``next_action`` per loop action, from the
program's own counter (``GapStats.schedule_ms``) over the window."""


def read(ctx):
    return ctx.schedule_ms / ctx.actions if ctx.actions else None
