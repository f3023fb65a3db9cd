"""Docs-accuracy guard: every CLI command documented in README.md /
docs/API.md must be accepted by the parser it names. The `--out ""` →
`--no-out` rename drifted silently once; this test runs ``--help`` on
each documented entrypoint and fails on any documented flag the parser
does not accept, so docs and argparse cannot diverge again. (CI runs it
inside tier-1 and in the dedicated docs-and-examples job.)"""
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "docs/API.md"]

# `python -m <module>` or `python <script>.py` at the start of a shell
# command (env-var prefixes like XLA_FLAGS=... allowed before `python`)
_CMD = re.compile(r"python (?:-m ([\w.]+)|((?:examples|benchmarks)"
                  r"/[\w/]+\.py|chip_smoke\.py))")
_FLAG = re.compile(r"--[A-Za-z][A-Za-z0-9-]*")


def _documented_commands():
    """(entrypoint, flags, doc, line) for every fenced-code command; the
    entrypoint is a module name or a script path, flags are the --flags
    given after it (line continuations joined)."""
    cmds = []
    for doc in DOCS:
        path = os.path.join(ROOT, doc)
        in_code, buf, lineno = False, "", 0
        for i, line in enumerate(open(path), 1):
            if line.strip().startswith("```"):
                in_code = not in_code
                continue
            if not in_code:
                continue
            if buf:
                buf += " " + line.strip()
            elif "python" in line:
                buf, lineno = line.strip(), i
            if buf.endswith("\\"):
                buf = buf[:-1].strip()
                continue
            if buf:
                m = _CMD.search(buf)
                if m:
                    tail = buf[m.end():]
                    cmds.append((m.group(1) or m.group(2),
                                 _FLAG.findall(tail), doc, lineno))
                buf = ""
    return cmds


def _accepted_flags(entry):
    """Flags the entrypoint's argparse accepts, read from ``--help`` run
    in a subprocess (entrypoints parse inside main(), and fl_dryrun must
    set XLA_FLAGS before its jax import — only --help is faithful)."""
    cmd = [sys.executable]
    if entry.endswith(".py"):
        cmd += [entry]
    else:
        cmd += ["-m", entry]
    if entry == "repro.launch.fl_dryrun":
        cmd += ["--devices", "1"]  # consumed pre-jax; keep --help fast
    cmd += ["--help"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                       env=env, timeout=300)
    assert r.returncode == 0, \
        f"`{' '.join(cmd)}` failed:\n{r.stdout[-1500:]}{r.stderr[-1500:]}"
    return set(_FLAG.findall(r.stdout))


def test_readme_names_real_entrypoints():
    """Sanity on the extractor itself: the README documents (at least)
    the dry-run and the benchmark harnesses."""
    entries = {c[0] for c in _documented_commands()}
    for expected in ("repro.launch.fl_dryrun", "benchmarks.perf_hillclimb",
                     "benchmarks.bench_ggc_scaling", "examples/quickstart.py"):
        assert expected in entries, sorted(entries)


def test_documented_flags_are_accepted():
    """Every --flag a doc attaches to a CLI command is accepted by that
    command's parser."""
    by_entry = {}
    failures = []
    for entry, flags, doc, line in _documented_commands():
        if entry not in by_entry:
            by_entry[entry] = _accepted_flags(entry)
        for f in flags:
            if f not in by_entry[entry]:
                failures.append(f"{doc}:{line}: {entry} does not accept "
                                f"{f} (accepted: "
                                f"{sorted(by_entry[entry])})")
    assert not failures, "\n".join(failures)


def test_fl_dryrun_accepts_adversary_flags():
    """The adversary surface (DESIGN.md §15) is reachable from the
    dry-run CLI: `--adversary`, `--adversary-fraction` and `--mix-rule`
    are accepted flags, whatever the docs currently fence."""
    flags = _accepted_flags("repro.launch.fl_dryrun")
    for f in ("--adversary", "--adversary-fraction", "--mix-rule"):
        assert f in flags, sorted(flags)


def test_bench_robustness_help_parses():
    """`benchmarks.bench_robustness --help` exits 0 and exposes the
    sweep axes the robustness CI job and the regression gate drive."""
    flags = _accepted_flags("benchmarks.bench_robustness")
    for f in ("--attacks", "--fractions", "--mix-rules", "--graph-reprs",
              "--smoke", "--mesh", "--out"):
        assert f in flags, sorted(flags)


@pytest.mark.slow
def test_quickstart_example_runs():
    """The README's first command actually runs (CI executes it at toy
    sizes in the docs-and-examples job; this is the in-suite variant)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run(
        [sys.executable, "examples/quickstart.py", "--rounds", "2",
         "--tau", "1", "--clients", "6"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=1200)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "DPFL(B=4)" in r.stdout
