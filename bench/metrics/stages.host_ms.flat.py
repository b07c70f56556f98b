"""``stages.host_ms`` in a cell offered more than it serves, where host
time per batch takes from the queries answered a second
(``answered_qps``): embed and rerank time per batch, in ms."""
from benchlib.spec import metric_reader

read = metric_reader("stages.host_ms")
