"""One case of ``test_perfbench.py`` cannot hold a model configuration.

``test_configuration_file_is_under_paths_and_used`` was written when
every configuration was a table at full shape: it reads a table's shapes
(``costs.shapes``: rows and feature columns) and asserts ``reduced ==
[]``. A model configuration cut to one chip's share of a deployment has
neither: its file holds the model's ``config.json`` keys, and the
contract makes it list every cut under ``reduced``. For such a
configuration (``reduced`` not empty) that case is deselected here, and
``test_txfit.py::test_model_configuration_file_is_under_paths_and_used``
holds it to the same points and to the stricter ones a cut brings.
"""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pytest_collection_modifyitems(config, items):
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        cut = {c["name"] for c in json.load(fh)["configs"] if c["reduced"]}
    gone = [it for it in items if it.name in {
        f"test_configuration_file_is_under_paths_and_used[{n}]" for n in cut}
        and it.fspath.basename == "test_perfbench.py"]
    if gone:
        items[:] = [it for it in items if it not in gone]
        config.hook.pytest_deselected(items=gone)
