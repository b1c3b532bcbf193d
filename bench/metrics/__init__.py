"""One reader per metric: ``read(ctx)`` returns the metric's value, or
None where the run holds nothing for it to read.  ``ctx`` carries
``calls`` and ``window_s`` (the timed window), ``setup_s``, ``obs`` (the
program's counter and histogram deltas over the window), ``spans`` (its
root spans closed in the window), ``trace`` (``bench.trace.reduce`` of
a ``--trace 1`` run, else None), ``peaks`` (the device's row of ``bench/peaks.json``), ``cell``
and ``config``, and ``least_bytes``: the least bytes of the Alg. 1
schedule summed over the window's calls, where the call's reference
counts them (else None)."""

FIXPOINT_SPANS = ("mis2.resident_fixed_point", "mis2.hybrid_fixed_point")


def span_seconds(ctx, name: str):
    """Seconds the window spent in the program's span ``name``, or None
    where it never opened."""
    labels = {"span": name}
    if ctx.obs.count("span.seconds", labels) == 0:
        return None
    return ctx.obs.value("span.seconds", labels)
