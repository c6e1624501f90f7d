"""How far the exit distribution is from collapsing onto one round: the
window's ``loop.exit_entropy`` (sum over the scored positions of H(p))
over those positions (``trainer.tokens``) times ln R, the entropy of a
uniform exit over the configuration's ``total_ut_steps`` rounds, in %.
0 is a gate that always stops at the same round."""
import math

from benchmark.layer_metrics._window import counter_change


def read(run):
    entropy = counter_change(run, "loop.exit_entropy")
    scored = counter_change(run, "trainer.tokens")
    rounds = run.cell.cfg.get("total_ut_steps", 0)
    if entropy is None or not scored or rounds < 2:
        return None
    return 100.0 * entropy / (scored * math.log(rounds))
