"""The harness as data: every cell resolves its files by name (its
architecture module by the configuration's ``model_type``), and the
traffic is a function of the seed that never outgrows the prefill bucket
or the configuration's vocabulary."""

import glob
import json
import os

import numpy as np
import pytest

import run as R
import traffic as T

SPEC = R.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    c, conf, mix, arch = R.resolve(SPEC, cell)
    entry = next(e for e in SPEC["configs"] if e["name"] == c["config"])
    assert entry["file"].startswith("bench/configs/")
    assert arch.__file__ == os.path.join(R.ARCH, f"{conf['model_type']}.py")
    assert set(entry["reduced"]) <= set(conf["reduced"])
    assert conf["mesh"]["data"] * conf["mesh"]["model"] == c["chips"]
    assert mix["slots"]["prefill_len"] <= mix["slots"]["max_len"]
    metrics = R.per_layer_metrics(SPEC, cell)
    assert metrics
    for m in metrics:
        assert callable(R.load_reader(m["name"]))
    R.model_config(conf)
    R.engine_config(conf, mix)


def test_every_metric_has_a_reader_and_its_cells_exist():
    names = set(CELLS)
    for m in SPEC["per_layer"]:
        assert os.path.exists(R.reader_path(m["name"]))
        assert set(m["workloads"]) <= names
    for w in SPEC["workloads"]:
        assert os.path.exists(os.path.join(R.BENCH, "traffic",
                                           f"{w['traffic']}.json"))


def _requests(cell, seed, n=130):
    """The cell's first ``n`` requests, ids drawn from its vocabulary."""
    _, conf, mix, arch = R.resolve(SPEC, cell)
    return T.Traffic(mix, arch.dims(conf)["V"], seed).first(n)


@pytest.mark.parametrize("cell", CELLS)
def test_one_seed_gives_the_same_requests(cell):
    a, b = _requests(cell, BIG_SEED), _requests(cell, BIG_SEED)
    c = _requests(cell, BIG_SEED + 1)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))


@pytest.mark.parametrize("cell", CELLS)
def test_prompts_fit_the_bucket_and_decks_hold_the_same_sizes(cell):
    _, conf, mix, arch = R.resolve(SPEC, cell)
    deck = mix["deck"]
    limit = mix["slots"]["prefill_len"]
    sizes = []
    for seed in (1, BIG_SEED):
        reqs = _requests(cell, seed, 2 * deck)
        assert max(len(r.prompt) for r in reqs) <= limit
        assert min(len(r.prompt) for r in reqs) >= 1
        assert max(int(r.prompt.max()) for r in reqs) < arch.dims(conf)["V"]
        assert max(len(r.prompt) + r.max_new for r in reqs) \
            <= mix["slots"]["max_len"]
        sizes.append(sorted((len(r.prompt), r.max_new) for r in reqs[:deck]))
    prompts = [sorted(p for p, _ in s) for s in sizes]
    outputs = [sorted(o for _, o in s) for s in sizes]
    assert prompts[0] == prompts[1] and outputs[0] == outputs[1]


def _test_mix(name):
    with open(os.path.join(R.BENCH, "tests", "data", f"{name}.json")) as f:
        return json.load(f)


def test_open_loop_arrivals_do_not_depend_on_the_seed():
    mix = _test_mix("tiny-open")
    a = T.arrival_times(mix["arrivals"], 51.0)
    b = T.arrival_times(mix["arrivals"], 51.0)
    assert np.array_equal(a, b) and len(a) > 0
    assert a.min() >= 0 and a.max() < 51.0
    # the mean rate over a long horizon is the file's rate
    long = T.arrival_times(mix["arrivals"], 20000.0)
    assert abs(len(long) / 20000.0 / mix["arrivals"]["rate"] - 1) < 0.05


def test_hot_topic_moves():
    mix = _test_mix("tiny-skew")
    gen = T.Traffic(mix, 32000, BIG_SEED)
    every = mix["tokens"]["topics"][1]["redraw_every"]
    first = np.concatenate([gen.request(i).prompt for i in range(every)])
    later = np.concatenate([gen.request(i).prompt
                            for i in range(every, 2 * every)])
    top_first = np.bincount(first, minlength=32000).argmax()
    top_later = np.bincount(later, minlength=32000).argmax()
    assert top_first != top_later


def test_config_files_hold_the_published_widths():
    """Each file holds the widths its architecture module publishes for
    its ``source``, but for the keys its ``reduced`` names."""
    paths = glob.glob(os.path.join(R.BENCH, "configs", "*.json"))
    assert paths
    for path in paths:
        with open(path) as f:
            conf = json.load(f)
        published = R.load_arch(conf).PUBLISHED[conf["source"]]
        for key, width in published.items():
            if key not in conf["reduced"]:
                assert conf[key] == width, (path, key)


def test_no_tpu_means_no_result(capsys):
    argv = ["--workload", CELLS[0], "--seed", str(BIG_SEED), "--seconds", "1",
            "--trace", "0"]
    assert R.main(argv) == 1
    assert capsys.readouterr().out == ""


def test_a_metric_without_a_file_reads_by_its_first_part():
    assert R.reader_path("device_idle_share.sat") \
        == os.path.join(R.BENCH, "metrics", "device_idle_share.py")
    assert R.reader_path("mfu") == os.path.join(R.BENCH, "metrics", "mfu.py")
