"""Driver `open_loop_http`: requests sent to `POST /v1/completions` of a
`Gateway` in front of `create_llm_engine`'s engine at the due times of a
fixed-rate schedule, streamed over SSE, each timed from when it was due.
The work is in chipbench/serving.py; the mix's `arrivals` give the rate."""

from chipbench.serving import (build, check, counts, drain,  # noqa: F401
                               end_to_end, setup, window)
from chipbench.serving_control import calibrate  # noqa: F401
