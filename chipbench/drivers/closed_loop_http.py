"""Driver `closed_loop_http`: `arrivals.clients` clients, each sending its
next request to `POST /v1/completions` when the last one ended, until the
window closes.  The work is in chipbench/serving.py."""

from chipbench.serving import (build, check, counts, drain,  # noqa: F401
                               end_to_end, setup, window)
from chipbench.serving_control import calibrate  # noqa: F401
