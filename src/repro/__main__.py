"""Top-level entrypoint: ``python -m repro <command> [flags...]``.

One front door for the whole system, dispatching to the thin CLI
adapters (each of which is argparse -> RunSpec -> facade):

  train    repro.launch.train    Trainer facade (fault-tolerant loop)
  serve    repro.launch.serve    Server facade (paged) / static oracle
  dryrun   repro.launch.dryrun   512-device lower+compile sweep
  bench    benchmarks.run        traffic harness + paper tables/kernels

Every ``train``/``serve`` flag set resolves to a RunSpec first and
every ``bench`` subcommand to a BenchSpec (``--dump-spec`` prints it),
so the CLI surface and the programmatic API (docs/api.md,
docs/benchmarks.md) can never drift. Every command runs with JAX's
persistent compilation cache on (repro/launch/cache.py). ``bench`` needs the repo root on
sys.path (run from the checkout, as ``benchmarks/`` sits next to
``src/``).
"""
from __future__ import annotations

import sys
from typing import Optional, Sequence

USAGE = """\
usage: python -m repro {train,serve,dryrun,bench} [flags...]

commands:
  train    train a model (argparse -> RunSpec -> repro.api.Trainer)
  serve    serve a model (argparse -> RunSpec -> repro.api.Server)
  dryrun   lower + compile every (arch x shape x mesh) cell at 512 devices
  bench    traffic harness (bench serving -> BENCH_serving.json) +
           paper-table / kernel benches; `bench --help` lists suites

`python -m repro <command> --help` shows that command's flags.
"""


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        print(USAGE, end="")
        return 0
    cmd, rest = argv[0], argv[1:]
    if cmd not in ("train", "serve", "dryrun", "bench"):
        print(f"python -m repro: unknown command {cmd!r}\n{USAGE}",
              file=sys.stderr, end="")
        return 2
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if cmd == "train":
        from repro.launch.train import main as run

        run(rest)
    elif cmd == "serve":
        from repro.launch.serve import main as run

        run(rest)
    elif cmd == "dryrun":
        from repro.launch.dryrun import main as run

        run(rest)
    elif cmd == "bench":
        try:
            from benchmarks.run import main as run
        except ModuleNotFoundError:
            print("python -m repro bench: the benchmarks/ package is not "
                  "importable — run from the repo root (it lives next to "
                  "src/, outside the installed package)", file=sys.stderr)
            return 2
        sys.argv = ["benchmarks.run", *rest]
        run()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
