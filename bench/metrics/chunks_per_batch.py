"""Device chunks (arrival-round chunks, one step each) per ``ingest`` call:
the engine's ``FlowStats.rounds`` counter over the window's calls."""


def read(ctx):
    calls = ctx.window.calls
    return sum(c.rounds for c in calls) / len(calls) if calls else None
