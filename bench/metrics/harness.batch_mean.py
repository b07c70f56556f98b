"""Mean size of the batches the harness's continuous batcher formed
(``ServingHarness.batch_sizes``) over the window."""


def read(ctx):
    b = ctx.batch_sizes
    return float(sum(b) / len(b)) if b else None
