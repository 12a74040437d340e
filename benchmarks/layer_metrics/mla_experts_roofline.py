"""Share of their roofline the grouped expert products reach under the
sigmoid router: the least time the chip's published peaks allow for the
assignments an even router sends to the held experts (``n_routed_experts``
held of ``router_num_experts``: the accepted ``moe_experts_roofline`` reads
another key) and those experts' weights read once a pass
(``harness.kanana2_parts.experts_train_required``) over the time of the ops
under ``hvd_moe_experts``.  A tile's empty rows and the forward products
computed again in the backward loop are not required work, so they lower
the share."""

from benchmarks.harness import kanana2_parts as parts


def read(run):
    return parts.experts_roofline(run)
