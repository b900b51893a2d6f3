#!/usr/bin/env python
"""Regenerate tests/accuracy_expectations.json (the h2o-test-accuracy
successor's stored expectations — SURVEY.md §4).

Run deliberately when an algorithm change is SUPPOSED to move metrics, and
review the JSON diff like any other expectation change:

    python tools/gen_accuracy_expectations.py
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> None:
    # same topology as tests/conftest.py: 8-device CPU mesh. XLA_FLAGS must
    # be in the environment before jax initializes — re-exec once with it
    # set (same pattern as __graft_entry__.dryrun_multichip).
    if os.environ.get("_H2O3_ACC_CHILD") != "1":
        env = dict(
            os.environ,
            _H2O3_ACC_CHILD="1",
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip(),
        )
        os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)], env)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "tests"))

    import h2o3_tpu

    h2o3_tpu.init(log_level="WARN")
    from accuracy_cases import run_cases

    results = run_cases(progress=True)
    out = ROOT / "tests" / "accuracy_expectations.json"
    out.write_text(json.dumps(results, indent=2, sort_keys=True) + "\n")
    print(f"wrote {out}")
    for case, metrics in sorted(results.items()):
        print(f"  {case}: {metrics}")


if __name__ == "__main__":
    main()
