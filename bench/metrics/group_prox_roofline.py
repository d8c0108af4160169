"""Share of its roofline that the group-prox Pallas kernel (the AMA
step's row-wise ball projection) reaches in the refreshed rounds: the
least time of its calls (``costs.group_ball_proj_batched`` at each call's
operand shape) over their device time in the trace."""
from bench import costs
from bench.trace import kernel_calls

KERNEL = "group_ball_proj"


def read(run):
    calls = [c for c in kernel_calls(run.trace, KERNEL)
             if c["operands"] and len(c["operands"][0]) == 3]
    if not calls:
        return None
    # the kernel pads the edge slots to whole blocks; the operation needs
    # the slots of the kNN graph
    options = run.config["finalize"].get("algo_options", {})
    slots = int(run.config["federation"]["clients"]) * int(
        options.get("knn_k", 0)) or None
    works = []
    for c in calls:
        b, e, d = c["operands"][0]
        works.append(costs.group_ball_proj_batched(
            b, min(e, slots) if slots else e, d))
    return costs.roofline_pct(works, sum(c["s"] for c in calls), run.peak)
