"""expert_load_max_mean (ratio): the straggler inside an expert layer,
the paper's max-over-mean at expert grain.  Over the traced
``serve.decode`` spans: the summed ``moe_max`` (the most pairs on one
held expert, summed over layers) over the summed mean load, ``moe_pairs``
over the experts held (the configuration's ``num_experts``).  1 is an
even load.  None where no decode span carries the counters."""
from bench import spans


def read(run):
    found = spans.for_run(run)
    if found is None:
        return None
    calls = [s.args for s in found.named("serve.decode")
             if "moe_pairs" in s.args]
    pairs = sum(a["moe_pairs"] for a in calls)
    if pairs <= 0:
        return None
    held = run.cell.config["num_experts"]
    return sum(a["moe_max"] for a in calls) * held / pairs
