"""Share of the step program's mean device time under the prediction
layer's name (``nn/attention_layers.py`` ``MultiTokenPrediction``: its input
projection ``mtp_in``, its block's scopes, its pass through the head and its
loss under ``lm_head``), forward and backward, from the trace's ``scopes``. A
scope path is cut at two components, ``<LayerClass>/<scope>``, so the layer
opens its scopes directly under its own. Its experts' grouped matmuls are
not in it: the chip's compiler names them ``ragged-dot-*`` with an
``op_name`` of its own (``moe_share.train`` reads them by kind, for all
expert layers together). Nothing to read where no such scope ran."""

LAYER = "MultiTokenPrediction"


def read(run, trace, cell, peak):
    scopes = trace.get("scopes")
    if not scopes:
        return None
    seconds = sum(s for phase in ("forward", "backward") for scope, s in scopes["scopes"][phase].items()
                  if scope.split("/", 1)[0] == LAYER)
    return 100.0 * seconds / scopes["step_s"] if seconds else None
