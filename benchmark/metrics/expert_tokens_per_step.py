"""``expert_tokens_per_step`` (layer: parallel): a count from the program:
assignments the held experts got in the last step, summed over the sparse
layers (the family's ``expert_kept`` counter). A uniform router sends
tokens x top-k x held / all to each layer. ``None`` where the extra state
carries no counter."""

from benchmark.harness import scopes


def read(context):
    counters = scopes.routing_counters(context)
    if counters is None:
        return None
    return float(counters["expert_kept"].sum())
