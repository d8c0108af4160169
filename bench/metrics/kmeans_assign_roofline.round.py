"""Share of its roofline that the ``kmeans_assign`` Pallas kernel reaches
in the Lloyd iterations of the refreshed rounds: the least time of its
calls (``costs.kmeans_assign`` at each call's operand shapes) over their
device time in the trace."""
from bench import costs
from bench.trace import kernel_calls

KERNEL = "kmeans_assign"


def read(run):
    calls = [c for c in kernel_calls(run.trace, KERNEL)
             if len(c["operands"]) >= 2]
    if not calls:
        return None
    clients = int(run.config["federation"]["clients"])
    works = []
    for c in calls:
        (rows, d), (k, _) = c["operands"][0], c["operands"][1]
        # the kernel pads the clients to whole blocks; the operation
        # needs the clients
        works.append(costs.kmeans_assign(min(rows, clients), d, k))
    return costs.roofline_pct(works, sum(c["s"] for c in calls), run.peak)
