"""PR 28 made `kimi_linear` a known architecture, and one case of
test_perfbench_manifest.py had taken that very name for its UNKNOWN one
(`test_an_unknown_model_type_names_the_files_to_add`).  A `model_config`
PR may add files under tests/perfbench/ and edit none, so the case is
marked as an expected failure here, and the rule it holds is tested with
a name nothing has in tests/test_kimi_linear.py
(`test_an_unknown_model_type_still_names_the_files_to_add`).  The next
`benchmark` PR should change the name in the case and delete this file."""
import pytest

STALE = "test_an_unknown_model_type_names_the_files_to_add"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == STALE and "perfbench" in str(item.fspath):
            item.add_marker(pytest.mark.xfail(
                reason="its unknown model_type, kimi_linear, exists since "
                       "PR 28; held in tests/test_kimi_linear.py",
                strict=True))
