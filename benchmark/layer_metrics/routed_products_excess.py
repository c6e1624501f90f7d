"""Products the routed layers' dense form computes over products routed
here: every held expert runs over every token (``moe.pairs_routed`` /
``num_experts_per_tok`` tokens a sparse layer, times ``num_experts_held``)
where ``moe.pairs_local`` token-expert pairs were routed to a held expert,
over the window.  ``n_routed_experts / num_experts_per_tok`` at even
routing (21.3 at 6 of 128); lower is better."""
from benchmark.layer_metrics._window import counter_change


def read(run):
    routed = counter_change(run, "moe.pairs_routed")
    local = counter_change(run, "moe.pairs_local")
    cfg = run.cell.cfg
    if not routed or not local or "num_experts_held" not in cfg:
        return None
    return (routed / cfg["num_experts_per_tok"] * cfg["num_experts_held"]
            / local)
