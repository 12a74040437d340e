"""Share of their roofline the grouped expert products reach beside gated
short-convolution and attention operators: the least time the chip's
published peaks allow for the assignments an even router sends to the held
experts (``num_experts`` held of ``router_num_experts``) over the *expert*
layers, and those experts' weights read once a pass
(``harness.lfm2_parts.experts_train_required``, from this configuration's
keys and its count of expert layers) over the time of the ops under
``hvd_moe_experts``.  A tile's empty rows and the forward products computed
again in the backward loop are not required work, so they lower the
share."""

from benchmarks.harness import lfm2_parts as parts


def read(run):
    return parts.experts_roofline(run)
