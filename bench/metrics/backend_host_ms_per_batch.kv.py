"""Host time of the execution backend per batch of the traced window, in
ms: the self time of the program's ``tdorch.backend.*`` spans (operand
building, uploads, dispatch, blocking fetches, the mirror's write-back;
harness/spans.py) over the batches."""
from program_metrics import self_ms_per


def read(ctx):
    return self_ms_per(ctx, ("tdorch.backend.",), ctx.calls)
