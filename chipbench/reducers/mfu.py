"""The whole step's share of the chip's bf16 peak over the measured window
(host clock, no trace needed): the operations the model needs for the
tokens the window processed (the ``costs`` of the family the facts name),
over window x chips x peak. ``kind`` ``serve``: prompts credited when their
first token arrives, each decoded token with the context it attended to.
``kind`` ``train``: forward and backward of the steps completed."""
from chipbench import harness


def reduce(args, facts, trace):
    s, peaks = facts["sizes"], facts["peaks"]
    if peaks is None:
        return None
    costs = harness.family(facts, "costs")
    if args["kind"] == "serve":
        flops = sum(costs.prompt_forward_flops(s, n)
                    for n in facts["prompts_done"])
        flops += sum(costs.token_forward_flops(s, c, True)
                     for c in facts["decode_contexts"])
    else:
        flops = facts["steps"] * costs.train_step_flops(
            s, facts["batch"], facts["sequence"])
    denom = facts["seconds"] * facts["chips"] * peaks["bf16_flops_per_s"]
    return 100.0 * flops / denom if flops and denom else None
