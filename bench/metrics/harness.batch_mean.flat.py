"""``harness.batch_mean`` in a cell offered more than it serves, where
fuller batches answer more queries a second (``answered_qps``): the mean
size of the batches the continuous batcher formed."""
from benchlib.spec import metric_reader

read = metric_reader("harness.batch_mean")
