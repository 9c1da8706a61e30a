#!/usr/bin/env python
"""rnb-lint CLI: run the static analyzer families over the repo.

Usage::

    python scripts/rnb_lint.py                       # everything
    python scripts/rnb_lint.py --family graph        # one family
    python scripts/rnb_lint.py --config my.json      # one user config
    python scripts/rnb_lint.py --verbose             # show baselined

Runs with no JAX device and no dataset: the graph checker imports
stage *modules* (so jax/flax import, but no backend initializes), the
AST and schema families read source only. Exit status: 0 clean, 1 any
active finding or stale baseline entry, 2 internal error.

Intentional exceptions live in ``rnb-lint-baseline.txt`` (repo root),
one ``RULE file anchor  # justification`` line each; a baseline entry
matching no current finding is *stale* and fails the run — the
baseline documents live exceptions, not history.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the graph checker imports stage modules, which import jax — keep
# it on the CPU platform: a static analyzer has no business holding
# the chip (one process at a time owns it)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

FAMILIES = ("graph", "hotpath", "schema", "concurrency")

#: rule-id prefix each family owns — single-family runs only consider
#: the baseline entries of the families that actually ran, so a clean
#: `--family graph` run is not failed by untested hotpath entries
#: reading as stale
FAMILY_RULE_PREFIX = {"graph": "RNB-G", "hotpath": "RNB-H",
                      "schema": "RNB-T", "concurrency": "RNB-C"}


def run(family_names, config_paths, baseline_path, verbose=False,
        out=sys.stdout):
    if "graph" in family_names:
        # only the graph family imports stage modules (and thus jax);
        # hotpath/schema are source-only — skip the ~5 s jax startup
        try:
            import jax
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
    from rnb_tpu.analysis.findings import Baseline, apply_baseline

    findings = []
    if "graph" in family_names:
        from rnb_tpu.analysis import graph
        findings.extend(graph.check_configs(config_paths, root=REPO))
    if "hotpath" in family_names:
        from rnb_tpu.analysis import hotpath
        findings.extend(hotpath.check_package(
            os.path.join(REPO, "rnb_tpu"), root=REPO))
    if "schema" in family_names:
        from rnb_tpu.analysis import schema
        findings.extend(schema.check_repo(REPO))
    if "concurrency" in family_names:
        from rnb_tpu.analysis import concurrency
        findings.extend(concurrency.check_package(
            os.path.join(REPO, "rnb_tpu"), root=REPO))

    baseline = Baseline.load(baseline_path)
    prefixes = tuple(FAMILY_RULE_PREFIX[f] for f in family_names)
    baseline.entries = {key: why for key, why in baseline.entries.items()
                        if key[0].startswith(prefixes)}
    active, suppressed, stale = apply_baseline(findings, baseline)

    for f in active:
        print(f.render(), file=out)
    if verbose:
        for f in suppressed:
            print("baselined: %s" % f.render(), file=out)
    for line in stale:
        print("stale baseline entry (finding fixed? prune it): %s"
              % line, file=out)
    print("rnb-lint: %d finding(s), %d baselined, %d stale baseline "
          "entr%s — %s"
          % (len(active), len(suppressed), len(stale),
             "y" if len(stale) == 1 else "ies",
             "FAIL" if (active or stale) else "OK"), file=out)
    return 1 if (active or stale) else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Static pipeline/config/telemetry analyzer "
                    "(rule catalog: README.md 'Static analysis')")
    parser.add_argument("--family", choices=FAMILIES, action="append",
                        help="run only this analyzer family "
                             "(repeatable; default: all)")
    parser.add_argument("--config", action="append", default=None,
                        help="check this pipeline config instead of "
                             "the shipped configs/*.json (repeatable)")
    parser.add_argument("--baseline",
                        default=os.path.join(REPO,
                                             "rnb-lint-baseline.txt"),
                        help="intentional-exception list")
    parser.add_argument("--verbose", action="store_true",
                        help="also print baseline-suppressed findings")
    parser.add_argument("--stamps", action="store_true",
                        help="print the declared concurrency-contract "
                             "registry (GUARDED_BY / UNGUARDED_OK per "
                             "class) and exit")
    args = parser.parse_args(argv)

    if args.stamps:
        from rnb_tpu.analysis import concurrency
        for file, cls, guarded, unguarded in \
                concurrency.contract_registry(
                    os.path.join(REPO, "rnb_tpu")):
            print("%s %s" % (file, cls))
            for attr in sorted(guarded):
                print("  %-24s guarded by %s" % (attr, guarded[attr]))
            for attr in sorted(unguarded):
                print("  %-24s unguarded: %s" % (attr, unguarded[attr]))
        return 0

    families = tuple(args.family) if args.family else FAMILIES
    configs = (args.config if args.config
               else sorted(glob.glob(os.path.join(REPO, "configs",
                                                  "*.json"))))
    try:
        return run(families, configs, args.baseline,
                   verbose=args.verbose)
    except Exception:
        # exit 2 = the analyzer itself failed, distinct from exit 1 =
        # findings (CI wrappers rely on the distinction)
        import traceback
        traceback.print_exc()
        return 2


if __name__ == "__main__":
    sys.exit(main())
