"""``expert_load_max_over_mean`` (layer: parallel): a count from the
program: the fullest held expert's assignments in the last step over the
mean of all held experts, over every sparse layer (the family's
``expert_kept`` counter). 1.0 is an even load; the grouped products' loop
pays for the fullest. ``None`` where the extra state carries no counter
or nothing was kept."""

from benchmark.harness import scopes


def read(context):
    counters = scopes.routing_counters(context)
    if counters is None or not counters["expert_kept"].sum():
        return None
    kept = counters["expert_kept"]
    return float(kept.max() / kept.mean())
