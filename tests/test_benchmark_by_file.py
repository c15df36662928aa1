"""A deployment arrives in the benchmark as files (perfbench/README.md, "How
to add a deployment without editing any file"): tier-1's guard of
perfbench/tests/test_by_file.py's whole check, so that the driver's count
holds a later PR to it. The check copies perfbench/ and BENCHMARK.json to
a temporary tree, adds a toy generator, form, law, configuration, cell and
per-layer metric as NEW files plus entries, rehearses the toy cell to a
result line on the CPU, and fails when any file that was there changed."""

import json
import os

from perfbench.tests import test_by_file

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_a_deployment_arrives_as_files(tmp_path):
    test_by_file.check(str(tmp_path))


def test_every_listed_name_has_its_file():
    """Every configuration, cell and per-layer metric BENCHMARK.json lists
    is a file under perfbench/, and names a generator, form and law that
    are files too."""
    import perfbench

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def load(*parts):
        with open(os.path.join(ROOT, *parts)) as f:
            return json.load(f)

    for config in bench["configs"]:
        body = load(config["file"])
        assert body["name"] == config["name"]
        assert body["generator"]["name"] in perfbench.names("generators")
        assert body["guarantees"]["law"] in perfbench.names("laws")
    for cell in bench["workloads"]:
        body = load("perfbench", "workloads", cell["name"] + ".json")
        assert body["traffic"]["input_form"] in perfbench.names("forms")
        assert (body["config"], body["chips"]) == (cell["config"],
                                                   cell["chips"])
    for metric in bench["per_layer"]:
        assert load("perfbench", "layer_metrics",
                    metric["name"] + ".json")["name"] == metric["name"]
