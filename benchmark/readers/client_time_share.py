"""Share of the window the client itself spent in its own calls."""


def read(ctx, activities):
    if not ctx["window_s"]:
        return None
    spent = sum(ctx["client_spent"].get(a, 0.0) for a in activities)
    return 100.0 * spent / ctx["window_s"]
