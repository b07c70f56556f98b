"""``retrieve.batch_ms`` in a cell offered more than it serves, where the
time a batch takes sets the queries answered a second (``answered_qps``):
the StageTimer's ``retrieval`` time per batch, index copy included, in ms."""
from benchlib.spec import metric_reader

read = metric_reader("retrieve.batch_ms")
