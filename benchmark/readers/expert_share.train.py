"""Share of the step program's mean device time in an expert layer without a
shared expert (``nn/moe_layers.py``), forward and backward: its scopes
``router``, ``dispatch``, ``experts`` and ``combine`` from the trace's
``scopes``, and the grouped matmuls themselves, which the chip's compiler
names ``ragged-dot-*`` with its own ``op_name`` and which are therefore read
by kind from ``kind_seconds`` (window seconds over the step program's runs in
the window). Nothing to read where no such scope ran."""

from benchmark.trace_reduce import scope_share


def read(run, trace, cell, peak):
    scopes = trace.get("scopes")
    share = scope_share(scopes, ("router", "dispatch", "experts", "combine"))
    if share is None:
        return None
    grouped_s = sum(seconds for kind, (seconds, _) in trace["kind_seconds"].items() if kind.startswith("ragged-dot"))
    if grouped_s and trace.get("program_runs"):
        per_step = grouped_s / trace["program_runs"]
        print(f"expert_share.train: grouped matmuls {per_step * 1e3:.3f} ms a step by kind, beside {share:.3f}% in scopes",
              flush=True)
        share += 100.0 * per_step / scopes["step_s"]
    return share
