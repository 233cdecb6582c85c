"""pallas_routes.train (count): blocks the op library routed to a fused
Pallas kernel while the step was traced (sum of the
``dispatch.pallas.hits.*`` counters over the warm-up)."""


def read(evidence):
    return evidence.get("pallas_routes")
