"""Share of their roofline the grouped expert products reach beside
attention layers of two kinds: the least time the chip's published peaks
allow for the assignments an even router sends to the held experts
(``num_experts`` held of ``router_num_experts``) and those experts' weights
read once a pass (``harness.qwen3_next_parts.experts_train_required``, from
this configuration's keys) over the time of the ops under
``hvd_moe_experts``.  A tile's empty rows and the forward products computed
again in the backward loop are not required work, so they lower the
share."""

from benchmarks.harness import mellum2_parts as parts


def read(run):
    return parts.experts_roofline(run, "swa_experts_roofline")
