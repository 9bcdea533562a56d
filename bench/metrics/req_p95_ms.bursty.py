"""``req_p95_ms``, read in the cells whose bursty traffic leaves the latency
tail too unsteady to bound end to end: the same reading, tied there to
``tokens_per_s``."""

import spec

read = spec.reader("req_p95_ms")
