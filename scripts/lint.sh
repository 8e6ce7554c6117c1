#!/usr/bin/env bash
# graftcheck gate: the AST lint over the whole package (telemetry
# schema, durability, and argv-protocol contract rules included), the
# schema pass's RECORDS.md drift gate, then the jaxpr collective/upcast
# census against the committed goldens. Nonzero exit on any finding or
# drift. Fast: the lint and schema passes are pure stdlib, the census
# only traces — no XLA compiles.
#
# Usage: scripts/lint.sh            (from anywhere)
#
# Tier-1 is this script, then the pytest line ROADMAP.md gives as
# "Tier-1 verify" (the driver runs that line with `-p xdist -n 6
# --dist loadfile`). There is no wrapper around the two.
#
# On a red:
#   - lint finding: fix it, or suppress the statement with
#     '# graftcheck: disable=<rule> -- <reason>' (rule catalog:
#     python -m tensorflow_distributed_tpu.analysis.lint --list-rules)
#   - RECORDS.md drift: edit observe/schemas.py, then regenerate:
#     python -m tensorflow_distributed_tpu.analysis.schema --update
#   - census drift: if the collective/upcast change is intentional,
#     regenerate and commit the goldens:
#     python -m tensorflow_distributed_tpu.analysis.jaxprcheck --update
set -o pipefail
cd "$(dirname "$0")/.."

rc=0

python -m tensorflow_distributed_tpu.analysis.lint \
  tensorflow_distributed_tpu/ || rc=$?

# Schema pass: the telemetry-contract rule subset plus the RECORDS.md
# drift gate (the lint above already ran the rules repo-wide; this adds
# the generated-doc check and gives the contract its own CLI surface).
python -m tensorflow_distributed_tpu.analysis.schema || rc=$?

env JAX_PLATFORMS=cpu python -m tensorflow_distributed_tpu.analysis.jaxprcheck \
  || rc=$?

exit "$rc"
