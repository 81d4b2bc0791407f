"""A configuration's model family is found by its published ``model_type``
(``bench/families/<model_type>.py``), so a family is added as one new file;
and Qwen2's weights, reference logits and counts are pinned to the values
they had before the family lookup, bit for bit."""
import hashlib
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import harness
from bench import model as bm
from bench.families import qwen2
from benchutil import REPO, TINY_MODEL, make_root, run_cell

SEED = 2**31 + 77
TINY = {"model": TINY_MODEL}
Q05 = json.loads((REPO / "bench/configs/qwen2-0.5b.json").read_text())

# the first 16 hex digits of the sha256 of each leaf's bytes, from the
# benchmark as it was before families (the ids and the law are the
# yardstick: a changed digest changes every weight of every cell)
WEIGHT_DIGESTS = {
    "['embed']['w']": "fad58435ba38d218",
    "['final_norm']['w']": "78d4675ef564f064",
    "['scan'][0]['ffn']['w_down']['w']": "50014b35bafe0176",
    "['scan'][0]['ffn']['w_gate']['w']": "ba2166134a622677",
    "['scan'][0]['ffn']['w_up']['w']": "a66a5f93b88eb6d9",
    "['scan'][0]['mixer']['wk']['b']": "328eda6eeb99b482",
    "['scan'][0]['mixer']['wk']['w']": "47dfcfc02ab52c31",
    "['scan'][0]['mixer']['wo']['w']": "1b9d36a116015664",
    "['scan'][0]['mixer']['wq']['b']": "9fb145f2ea27dcd1",
    "['scan'][0]['mixer']['wq']['w']": "666d345f27fa3c48",
    "['scan'][0]['mixer']['wv']['b']": "da40477188bbaeaf",
    "['scan'][0]['mixer']['wv']['w']": "2a1204cd3e06b3e7",
    "['scan'][0]['norm1']['w']": "cbc3d0ae409fce91",
    "['scan'][0]['norm2']['w']": "fa69398ae97e7f65",
}
UNTIED_HEAD_DIGEST = "40771f15e5ef8d44"
# XLA's CPU backend splits float32 sums by the number of cores it may use,
# so the reference's bits are pinned with one core
REFERENCE_DIGESTS = {"f32": "654ed96c321f6943", "fp8": "bc5eb9b8098a6ec4"}
COUNTS = [
    ("decode_flops", TINY, ([5, 300, 4096],), 2793984),
    ("prefill_flops", TINY, ([(0, 256), (256, 300), (1000, 1017)],),
     78740992),
    ("paged_decode_attention_cost", TINY, (12345,), (6320640, 3160320)),
    ("decode_flops", Q05, ([1024] * 32,), 34432090112),
    ("prefill_flops", Q05, ([(0, 256)],), 186309050368),
    ("paged_decode_attention_cost", Q05, (32 * 1024,),
     (2818572288, 402653184)),
]

REFERENCE_ON_ONE_CORE = """
import hashlib, json, os, sys
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
import numpy as np
from bench.families import qwen2
conf, seed = json.loads(sys.argv[1]), int(sys.argv[2])
seq = np.random.default_rng(5).integers(0, 256, 45).astype(np.int32)
want = np.arange(20, 45)
print(json.dumps({
    name: hashlib.sha256(np.asarray(qwen2.Reference(conf, seed, fp8=fp8)
        .logits([seq], [want], 512)[0]).tobytes()).hexdigest()[:16]
    for name, fp8 in (("f32", False), ("fp8", True))}))
"""

NOBIAS_FAMILY = '''
"""Qwen2 without its Q/K/V biases, as Llama and Mistral have them: a family
added as one file."""
import dataclasses

import jax.numpy as jnp

from bench.families import qwen2

Q_BLOCK = qwen2.Q_BLOCK
decode_flops = qwen2.decode_flops
prefill_flops = qwen2.prefill_flops
paged_decode_attention_cost = qwen2.paged_decode_attention_cost


def arch_config(name, conf):
    return dataclasses.replace(qwen2.arch_config(name, conf),
                               qkv_bias=False)


def program_tree(key, conf, dtype):
    tree = qwen2.program_tree(key, conf, dtype)
    for w in ("wq", "wk", "wv"):
        del tree["scan"][0]["mixer"][w]["b"]
    return tree


class Reference(qwen2.Reference):
    def __init__(self, conf, seed, fp8=False):
        super().__init__(conf, seed, fp8=fp8)
        with_bias = self._layer_w
        self._layer_w = lambda key, i: {
            k: jnp.zeros_like(v) if k.endswith("_b") else v
            for k, v in with_bias(key, i).items()}
'''


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


def _files(root: pathlib.Path) -> dict[str, bytes]:
    return {str(p.relative_to(root)): p.read_bytes()
            for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_qwen2_weights_are_the_parents():
    params = bm.make_params(qwen2, SEED, TINY,
                            qwen2.arch_config("tiny", TINY))
    got = {jax.tree_util.keystr(k): _digest(v)
           for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == WEIGHT_DIGESTS
    untied = {"model": dict(TINY_MODEL, tie_word_embeddings=False)}
    params = bm.make_params(qwen2, SEED, untied,
                            qwen2.arch_config("tiny", untied))
    assert _digest(params["lm_head"]["w"]) == UNTIED_HEAD_DIGEST


@pytest.fixture(scope="module")
def reference_digests():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    out = subprocess.run(
        [sys.executable, "-c", REFERENCE_ON_ONE_CORE, json.dumps(TINY),
         str(SEED)], env=env, cwd=REPO, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("precision", ["f32", "fp8"])
def test_qwen2_reference_logits_are_the_parents(reference_digests,
                                                precision):
    assert reference_digests[precision] == REFERENCE_DIGESTS[precision]


@pytest.mark.parametrize("name,conf,args,want", COUNTS,
                         ids=[f"{c[0]}-{'tiny' if c[1] is TINY else 'q05'}"
                              for c in COUNTS])
def test_qwen2_counts_are_the_parents(name, conf, args, want):
    assert getattr(qwen2, name)(conf, *args) == want


def test_a_family_added_as_one_file_runs_a_cell(tmp_path, monkeypatch):
    root = make_root(tmp_path, model=dict(TINY_MODEL,
                                          model_type="test_nobias"))
    (root / "bench/families/test_nobias.py").write_text(NOBIAS_FAMILY)
    found = []
    real = harness.Bench.family

    def spy(self, conf):
        mod = real(self, conf)
        found.append(pathlib.Path(mod.__file__))
        return mod

    monkeypatch.setattr(harness.Bench, "family", spy)
    rc, out = run_cell(root)
    assert rc == 0 and out["correct"], out and out["checks"]
    assert out["checks"]["checked_tokens"]["value"] > 0
    assert found == [root / "bench/families/test_nobias.py"]
    # the family is new, and no file of bench/ changed
    ours, theirs = _files(root / "bench"), _files(REPO / "bench")
    assert all(ours.get(k) == v for k, v in theirs.items())
    assert set(ours) - set(theirs) == {
        "families/test_nobias.py", "configs/tiny.json",
        "traffic/tinychat.json", "limits/tiny.chat.json"}


def test_an_unknown_model_type_stops_before_the_lease(tmp_path, monkeypatch,
                                                      capsys):
    from bench import run

    def leased(*a, **k):
        raise AssertionError("the run went on to weights and the lease")

    monkeypatch.setattr(run, "Setup", leased)
    root = make_root(tmp_path, model=dict(TINY_MODEL,
                                          model_type="no_such_family"))
    rc, out = run_cell(root)
    assert rc != 0 and out is None
    assert "bench/families/no_such_family.py" in capsys.readouterr().err
