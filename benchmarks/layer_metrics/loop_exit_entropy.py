"""Mean entropy of a looped decoder's exit distribution at the last
checked step, in nats: over the positions that have a target, ``H(p) =
-sum_t p_t log p_t`` of the weights the loss puts on the passes' exits
(``loop_exits``, which the program computes in ``ouro_lm_loss`` and
returns with its state, as an expert cell returns its loads). Uniform
over four exits reads ln 4 = 1.386; near 0 three exits carry no weight,
their head passes multiply by nothing and the cell no longer measures
four. ``None`` from a run that kept no such numbers."""


def read(run):
    exits = run.get("loop_exits")
    return exits[0][-1] if exits else None
