"""Padded over exact ppermute bytes of the plans the window ran
(``tree_bytes_padded / tree_bytes_exact``, dispatch and combine): an exact
count from the plans' step tables."""


def read(ctx):
    plans, ran = ctx.layer.get("plans"), ctx.layer.get("ran")
    if not plans or not ran:
        return None
    padded = sum(p.tree_bytes_padded for d in ran for p in plans[d])
    exact = sum(p.tree_bytes_exact for d in ran for p in plans[d])
    return padded / exact if exact else None
