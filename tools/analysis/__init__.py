"""graftlint: repo-invariant static analysis for the gofr_tpu tree.

The reference Go stack gets `go vet` + the race detector for free; this
package is the Python/JAX analog for the invariants this repo actually
lives by, none of which a stock linter knows about:

- ``hotloop``   — no host syncs (`.item()`, `np.asarray`, `jax.device_get`,
                  `block_until_ready`, device-value coercions) in functions
                  reachable from the engine-loop entry points.
- ``clock``     — no `time.time()` in `gofr_tpu/tpu/` latency/telemetry
                  paths; wall-clock display anchors carry a pragma.
- ``ownership`` — `@loop_only`-marked methods (and their declared owned
                  fields) are only reached from loop-rooted call paths.
- ``lockorder`` — the `with self._lock` nesting graph has no cycles and
                  every nested acquisition is acknowledged.
- ``surface``   — metric names, config keys, and `/debug/*` endpoints are
                  documented where the runtime inventory tests expect them.
- ``oneengine`` — `gofr_tpu/tpu/engine.py` imports no model function but
                  the ones excepted by name, and nothing outside
                  `tpu/paging.py` constructs `LLMEngine`.

Run it with ``python -m tools.analysis`` (see runner.py for the CLI) or
through :func:`tools.analysis.runner.run` from tests. Everything here is
stdlib-``ast`` only — no new dependencies, deterministic output, stable
finding IDs that survive line drift (see findings.py).
"""

from .findings import Finding  # noqa: F401
from .runner import run  # noqa: F401
