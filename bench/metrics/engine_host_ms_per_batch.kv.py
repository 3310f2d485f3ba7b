"""Host time of the engines per batch of the traced window, in ms: the self
time of the program's front-door, stage and phase spans (``tdorch.kv.*``,
``tdorch.stage*``, ``tdorch.phase*``; harness/spans.py) over the batches."""
from program_metrics import self_ms_per


def read(ctx):
    return self_ms_per(ctx, ("tdorch.kv.", "tdorch.stage", "tdorch.phase"),
                       ctx.calls)
