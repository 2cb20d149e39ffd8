"""Share of their roofline the held experts' grouped products reach under
the sigmoid rule at a router 256 wide, in percent: what
``moe_held_experts_roofline`` reads
(``moe_experts_roofline.experts_work`` at the configuration's
``hidden_size`` 2048 and ``moe_intermediate_size`` 768 and the rows that
landed on the held experts in the last checked step, the
multi-token-prediction module's block included, over ``moe_experts_ms``),
under a name of this cell's own. The printed line is that reader's."""

from layer_metrics import moe_held_experts_roofline


def read(run):
    return moe_held_experts_roofline.read(run)
