"""How uneven the routing was over the held experts: the busiest one's
assignments over the mean of all held, across the window and summed over
layers (``lm_moe_expert_assignments_total{expert}``).  1 is an even load;
the busiest expert sets how long the expert layer's longest segment is.
A program without the counter is not read."""


def read(window):
    held = window.cell.config.get("n_routed_experts")
    if not held:
        return None
    loads = [window.counter_delta("lm_moe_expert_assignments_total",
                                  expert=str(e)) or 0.0 for e in range(held)]
    total = sum(loads)
    if total <= 0:
        return None
    return max(loads) / (total / held)
