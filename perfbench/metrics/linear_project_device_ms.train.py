"""linear_project_device_ms.train: device self-time under the ``linear_project`` (a Gated DeltaNet layer's six in-projections, beta
and g, and its output projection), ``linear_conv`` (the 4-tap conv, silu, the L2 norms) and ``linear_out`` (the gated norm) scopes,
forward and backward, per run of the step program on the first chip: the linear-attention mixer but for the rule itself."""

from perfbench import scopes

layer = "linear and gated attention"
unit = "ms"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("linear_project", "linear_conv", "linear_out"), ("pinned_step_fn",))
