"""Experts: the imbalance of a decode step's routing over the experts
held here. The largest count any held expert took in a layer, summed
over layers and steps (`moe_max_expert_load`), over the mean count an
expert took (`moe_local_assignments` / experts held): 1 is perfectly
even; the busiest expert sets how many rows its matrices multiply."""


def read(ctx):
    c, moe = ctx["counters"], ctx["counts"].get("moe")
    if not moe or not c.get("moe_local_assignments") \
            or c.get("moe_max_expert_load") is None:
        return None
    return (c["moe_max_expert_load"] * moe["experts_held"]
            / c["moe_local_assignments"])
