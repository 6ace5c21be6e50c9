"""Host milliseconds per batch of the query plane's own work, outside
the replay and the epilogue: planning (``dp/serve/plan``: configs
prepared, budgets charged, the plan compiled), slicing each lane's
accumulators out of the batched fold (``dp/serve/lane_slice``), the
source digest check (``dp/verify_source``), the audit records
(``dp/audit_append``) and the release-journal commits
(``dp/journal_commit``). A program without the ``dp/serve/`` spans reads
nothing."""

from benchmark.metrics import _stages

STAGES = ("dp/serve/plan", "dp/serve/lane_slice", "dp/verify_source",
          "dp/audit_append", "dp/journal_commit")


def _host_s(stages: dict):
    if "dp/serve/plan" not in stages:
        return None
    return sum(stages.get(k, 0.0) for k in STAGES)


def read(ctx):
    s = _stages.mean_over_items(ctx, _host_s)
    return None if s is None else s * 1e3
