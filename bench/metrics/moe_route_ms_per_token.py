"""Self time of the program's `route` span per token: the MoE router and the
dispatch of tokens to the held experts' slots."""
from benchlib import readers


def read(ctx):
    tokens = sum(getattr(c, "tokens", 0) for c in ctx.calls)
    per_point = readers.self_ms_per_point(ctx, ("route",))
    if per_point is None or not tokens:
        return None
    return per_point * readers.points_done(ctx) / tokens
