"""Docs hygiene: every relative link in README.md / docs/ resolves, and the
documented entry points exist (the CI link-check step runs the same tool;
this keeps it enforced in tier-1 too)."""

import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_markdown_links_resolve():
    out = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "check_markdown_links.py"),
         str(ROOT)],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr + out.stdout


def test_link_checker_scans_all_files_in_one_pass(tmp_path):
    """CHANGES.md and ISSUE.md are scanned along with README/docs, and
    *every* broken link is reported in a single run (no stop-at-first)."""
    (tmp_path / "README.md").write_text("[a](missing-a.md)")
    (tmp_path / "CHANGES.md").write_text("[b](missing-b.md)")
    (tmp_path / "ISSUE.md").write_text("[c](missing-c.md) [ok](README.md)")
    out = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "check_markdown_links.py"),
         str(tmp_path)],
        capture_output=True, text=True)
    assert out.returncode == 1
    assert "checked 3 markdown files, 3 broken links" in out.stdout
    for frag in ("missing-a.md", "missing-b.md", "missing-c.md"):
        assert frag in out.stderr, (frag, out.stderr)


def test_readme_and_docs_exist():
    for name in ("README.md", "docs/serving.md", "docs/kernels.md",
                 "ROADMAP.md", "PAPER.md", "CHANGES.md"):
        assert (ROOT / name).is_file(), name


def test_documented_modules_import():
    """Commands shown in README/docs refer to these modules; a rename must
    update the docs (the link checker cannot see module paths)."""
    import importlib
    for mod in ("repro.serve", "repro.kernels.paged_attention",
                "repro.kernels.flash_attention", "repro.runtime.telemetry",
                "repro.launch.serve", "repro.launch.train"):
        importlib.import_module(mod)
    for path in ("src/repro/launch/serve.py", "src/repro/launch/train.py",
                 "benchmarks/serve_throughput.py", "examples/quickstart.py",
                 "chip_smoke.py"):
        assert (ROOT / path).is_file(), path
