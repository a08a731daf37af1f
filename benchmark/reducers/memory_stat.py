"""A `memory_stats()` field after the window, fullest device."""


def read(ctx, field, scale=1.0):
    vals = [m.get(field) for m in ctx["memory_stats"] if m]
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    return scale * max(vals)
