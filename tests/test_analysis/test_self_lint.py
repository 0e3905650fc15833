"""The repo lints itself clean (ISSUE 3 acceptance): every pre-existing
violation is either fixed or carries a justified suppression, and any NEW
hazard fails this test (and the CI sheeplint job) immediately."""

import os

from sheeprl_tpu.analysis.linter import lint_paths

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_repo_is_sheeplint_clean():
    targets = [
        os.path.join(REPO, "sheeprl_tpu"),
        os.path.join(REPO, "tools"),
    ]
    violations = lint_paths(targets)
    assert not violations, "\n" + "\n".join(v.format() for v in violations)
