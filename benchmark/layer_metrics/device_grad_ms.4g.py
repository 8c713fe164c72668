"""`device_grad_ms` where four groups train together; moves `tokens_per_s.4g`."""

from benchmark.spec import reader_beside

_same = reader_beside(__file__, "device_grad_ms")
LAYER, UNIT, SOURCE, read = _same.LAYER, _same.UNIT, _same.SOURCE, _same.read
MOVES = "tokens_per_s.4g"
