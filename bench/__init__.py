"""pilosa-tpu benchmark suite.

``python bench.py`` and ``python -m bench`` run the full gauntlet
suite; ``--overhead-smoke`` / ``--memory-smoke`` / ``--chaos-smoke``
/ ``--write-smoke`` / ``--ragged-smoke`` run the check.sh tier-1
gates.  Shared harness pieces live in bench/common.py; see
bench/main.py for the module map.
"""

from bench.common import (  # noqa: F401 — the package's public face
    NORTH_STAR_CHIPS,
    NORTH_STAR_MS,
    build_index,
    log,
)
