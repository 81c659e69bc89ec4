"""Share of the fused search's rows that failed the exactness certificate and
were answered by the exact scan.  The tournament kernel is the kernel under
test: a window in which the fused search took another kernel is an error."""


def read(ctx):
    c = ctx["snapshot"]["counters"]
    fused = c.get("fused_rows", 0.0)
    if not fused:
        return None
    if c.get("tourney_rows") != fused:
        raise RuntimeError(
            f"fused search answered {fused} rows but the tournament kernel "
            f"only {c.get('tourney_rows')}")
    return 100.0 * c.get("cert_fallback_rows", 0.0) / fused
