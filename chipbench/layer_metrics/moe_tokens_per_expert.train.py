"""moe_tokens_per_expert.train (count): tokens routed to an expert held
here, a step and expert layer: the window's ``moe.tokens_held`` over steps,
experts held and expert layers.  The counters are brought up to date by
``step.sync()``, which brackets a traced window; nothing where the program
has no such counter."""


def read(evidence):
    moe, n = evidence.get("moe"), evidence.get("steps")
    if not moe or not n or not moe.get("tokens_routed"):
        return None
    return moe["tokens_held"] / n / moe["experts_held"] / moe["expert_layers"]
