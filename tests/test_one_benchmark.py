"""The repo has one benchmark (``BENCHMARK.json`` + ``benchmark/``).

The harness that stood beside it until PR 32, its records and the
numbers only it bore out are gone, and nothing that a session plans from
points at them: a pointer to a file that is not there sends the next
reader looking for numbers nobody can reproduce. ``CHANGES.md``,
``ROADMAP.md``, ``PERF.md`` and ``SURVEY.md`` keep the history and may
name them.
"""
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GONE = ("bench.py", "BENCH_r05.json", "BASELINE.json", "BASELINE.md",
        "MULTICHIP_r01.json", "MULTICHIP_r02.json", "MULTICHIP_r03.json",
        "MULTICHIP_r04.json", "MULTICHIP_r05.json", "VERDICT.md",
        "ADVICE.md", "tests/test_bench_harness.py")
POINTER = re.compile(
    r"(?<![\w/])bench\.py|BENCH_r0|MULTICHIP_r0|\bLKG\b|BASELINE\.(json|md)"
    r"|VERDICT\.md|ADVICE\.md|test_bench_harness")
TEXT = (".py", ".md", ".json", ".txt", ".toml", ".cfg", ".cc", ".h")


def _files(root):
    path = os.path.join(REPO, root)
    if os.path.isfile(path):
        yield path
        return
    for folder, dirs, names in os.walk(path):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            if name.endswith(TEXT):
                yield os.path.join(folder, name)


def test_the_old_harness_and_its_records_are_gone():
    assert [p for p in GONE if os.path.exists(os.path.join(REPO, p))] == []


@pytest.mark.parametrize("root", [
    "README.md", "COVERAGE.md", "PARITY.md", "paddle_tpu", "tests", "tools",
    "benchmark", "chip_smoke.py", "__graft_entry__.py",
    ".claude/skills/verify/SKILL.md"])
def test_nothing_points_at_them(root):
    found = []
    for path in _files(root):
        if os.path.samefile(path, __file__):
            continue
        with open(path, encoding="utf-8", errors="replace") as f:
            for n, line in enumerate(f, 1):
                if POINTER.search(line):
                    found.append(f"{os.path.relpath(path, REPO)}:{n}")
    assert found == []


def test_readme_names_every_cell_and_the_command():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    measured = readme[readme.index("## What is measured"):]
    for cell in bench["workloads"]:
        assert f"`{cell['name']}`" in measured
    assert " ".join(bench["command"]) + " --workload" in measured
    assert "PERF.md" in measured and "PERF_LEDGER.jsonl" in measured
