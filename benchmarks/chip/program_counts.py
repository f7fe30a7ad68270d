"""Window differences of the program's telemetry, as a traced run snapshots
its registry at the window's two ends (``ctx["before"]["telemetry"]`` and
``ctx["after"]["telemetry"]``): a histogram's exact ``count`` and ``sum``
(a span ``<name>`` records into the histogram ``<name>_ms``), a counter's
``value``."""


def delta(ctx, metric: str, field: str):
    """``field`` of the program's ``metric`` at the window's end less at its
    start; None where the run took no telemetry or the program never
    recorded the metric.  A metric first recorded inside the window starts
    from 0, as does a histogram that had seen nothing (it has no sum)."""
    before = ctx["before"].get("telemetry")
    after = ctx["after"].get("telemetry")
    if before is None or after is None or metric not in after:
        return None
    return (after[metric].get(field, 0)
            - before.get(metric, {}).get(field, 0))
