"""Host time of the plan runner and the edge map per PageRank round of the
traced window, in ms: the self time of the program's ``tdorch.plan.*`` and
``tdorch.edgemap*`` spans (harness/spans.py) over the rounds."""
from program_metrics import self_ms_per


def read(ctx):
    return self_ms_per(ctx, ("tdorch.plan.", "tdorch.edgemap"),
                       ctx.after.get("rounds", 0) - ctx.before.get("rounds", 0))
