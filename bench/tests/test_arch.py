"""The architecture plug-in point (``run.load_arch``): the Mixtral module
serves the weights and reference readings the harness gave before the
Mixtral code moved into ``bench/arch/mixtral.py``; a configuration of a
new architecture brings its module as a new file, and the harness, the
weights' layout and the readers' counts come from it; a configuration
without a module is refused with the path it needs."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

import reference as Ref
import run as R
import weights as W

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2 ** 31 + 977

# recorded before the move, with the same seed and sequence: the sha256 of
# the tiny-1c serving tree (``_tree_digest``), and the reference's gaps
# and the fp8 control's, of ``_SEQ``'s last 16 tokens
TREE_SHA256 = ("bcd44a85b0d937bb9e922837417900d6"
               "cec54e37564cb6089fc955de710cd217")
GAP = [
    3.8510963916778564, 1.1295835971832275, 3.5585429668426514,
    3.7443151473999023, 3.8718221187591553, 4.177863121032715,
    2.351407527923584, 3.321192502975464, 1.438109278678894,
    1.879894495010376, 2.1847658157348633, 3.7604141235351562,
    3.534055709838867, 4.483139514923096, 1.3961867094039917,
    2.0488548278808594]
CONTROL_GAP = [
    0.0, 0.2043170928955078, 0.0, 0.0, 0.0, 0.0, 0.0, 0.26863551139831543,
    0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.19275164604187012]
_SEQ = ((np.arange(40) * 37 + 11) % 256).astype(np.int32)


def _tiny():
    with open(os.path.join(DATA, "tiny-1c.json")) as f:
        return json.load(f)


def _tree_digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_mixtral_weights_and_reference_are_unchanged_by_the_move():
    conf = _tiny()
    arch = R.load_arch(conf)
    assert _tree_digest(W.serving_params(arch, conf, SEED)) == TREE_SHA256
    out = Ref.teacher_forced(arch, conf, SEED, [_SEQ], [16], control=True)
    assert np.array_equal(out["gap"], np.float32(GAP))
    assert np.array_equal(out["control_gap"], np.float32(CONTROL_GAP))


TOY = """
PUBLISHED = {}
TENSORS = ("embed",)


def dims(conf):
    return {"V": conf["vocab"], "L": 1, "d": 8}


def model_config(conf, engine):
    return ("toy", conf["name"], engine["block_size"])


def expert_bytes(D):
    return 1000


def fixed_bytes(D):
    return 5000


def kv_read_bytes(D, context, block):
    return 10 * context


def prefill_flops(D, prompt):
    return 7 * prompt


def decode_flops(D, context):
    return 3 * context
"""


class _Trace:
    """A reduced trace whose programs and kernels each took 1 s."""

    def modules_matching(self, pattern):
        return 1e9, 1.0

    def ops_matching(self, pattern):
        return 1e9


def test_a_new_architecture_is_new_files_only(tmp_path):
    (tmp_path / "toy_moe.py").write_text(TOY)
    conf = {"name": "toy-1c", "model_type": "toy_moe", "vocab": 77,
            "engine": {"block_size": 4}}
    path = tmp_path / "toy-1c.json"
    path.write_text(json.dumps(conf))
    spec = {"configs": [{"name": "toy-1c", "file": str(path)}],
            "workloads": [{"name": "toy.cell", "config": "toy-1c",
                           "traffic": "decode", "chips": 1}]}
    cell, got, mix, arch = R.resolve(spec, "toy.cell", str(tmp_path))
    assert got == conf and mix is not None
    assert arch.__file__ == str(tmp_path / "toy_moe.py")
    assert R.load_arch(conf, str(tmp_path)) is arch
    assert R.model_config(conf, str(tmp_path)) == ("toy", "toy-1c", 4)
    D = arch.dims(conf)
    assert D["V"] == 77

    steps = [R.Step(0.0, 1.0, prefills=[5], counts=np.ones((1, 4))),
             R.Step(1.0, 2.0, decodes=[3, 4],
                    counts=np.asarray([[1, 0, 2, 0]]))]
    ctx = R.Context(conf=conf, arch=arch, D=D, mix=mix, chips=1,
                    peak={"hbm_bytes_per_s": 1e6, "bf16_flops_per_s": 1e6},
                    steps=steps, requests=[], spans=[], trace=_Trace(),
                    block_size=4)
    # prefill 7 x 5, decodes 3 x (3 + 4), over 2 s at 1e6 FLOP/s
    assert R.load_reader("mfu")(ctx) == pytest.approx(100 * 56 / 2e6)
    # KV 10 x (3 + 4) bytes in 1 s of kernel at 1e6 B/s
    assert R.load_reader("paged_attn_roofline")(ctx) \
        == pytest.approx(100 * 70 / 1e6)
    # 2 experts hit x 1000 + fixed 5000 + KV 70, in 1 s of decode program
    assert R.load_reader("decode_hbm_roofline")(ctx) \
        == pytest.approx(100 * 7070 / 1e6)


@pytest.mark.parametrize("model_type, shown", [
    ("nonesuch", "bench/arch/nonesuch.py"),
    (None, "bench/arch/<model_type>.py"),
    ("../run", "bench/arch/<model_type>.py")])
def test_a_configuration_without_its_module_is_refused(model_type, shown):
    conf = {"name": "x"}
    if model_type is not None:
        conf["model_type"] = model_type
    with pytest.raises(SystemExit, match=shown.replace(".", r"\.")):
        R.load_arch(conf)
