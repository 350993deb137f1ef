"""The dense family reads what the benchmark read before it became a family:
the same weights bit for bit, the same reference logits and the same
operation and byte counts, at rehearsal sizes (and, for the counts, at the
published ones).  The values were recorded from the harness as it stood
before the move, with the same seed and tokens."""

import hashlib

import jax
import numpy as np
import pytest

from chipbench import loader

CELL = "qwen3-4b-complete-poisson"
SEED = 2**33 + 7

# sha256 of each leaf's bytes (first 16 hex digits), dtype and shape.
LEAVES = {
    "['blocks']['period']['p0']['attn']['k_norm']": "b638277a8690e175 float32 (2, 16)",
    "['blocks']['period']['p0']['attn']['q_norm']": "b638277a8690e175 float32 (2, 16)",
    "['blocks']['period']['p0']['attn']['wk']": "3717f23e07290ecd bfloat16 (2, 64, 2, 16)",
    "['blocks']['period']['p0']['attn']['wo']": "5bec65357bc4139b bfloat16 (2, 4, 16, 64)",
    "['blocks']['period']['p0']['attn']['wq']": "a9b5ea617fa863a0 bfloat16 (2, 64, 4, 16)",
    "['blocks']['period']['p0']['attn']['wv']": "49ba8e964d847b92 bfloat16 (2, 64, 2, 16)",
    "['blocks']['period']['p0']['ln1']['scale']": "02722f124d0f1736 float32 (2, 64)",
    "['blocks']['period']['p0']['ln2']['scale']": "02722f124d0f1736 float32 (2, 64)",
    "['blocks']['period']['p0']['mlp']['wi']": "071f0da62872b8bc bfloat16 (2, 64, 2, 128)",
    "['blocks']['period']['p0']['mlp']['wo']": "6eac78929bcc086c bfloat16 (2, 128, 64)",
    "['embed']['table']": "ee3b251328f4e61c bfloat16 (256, 64)",
    "['final_norm']['scale']": "2f20cd03c9cd392a float32 (64,)",
}

# Per compared position of the fixed tokens: the argmax, the largest logit
# and the sum over the vocabulary.
LOGITS = {
    None: ([[95, 224, 7], [212, 30, 125]],
           [2.204888105392456, 2.709064483642578, 2.4835972785949707,
            3.8357908725738525, 3.07828426361084, 2.816305160522461],
           [-22.438698687707074, -11.02697407733649, -26.829570733592845,
            -32.52618593571242, -4.565269573358819, -20.220697483513504]),
    "fp8": ([[95, 224, 7], [212, 30, 125]],
            [2.2945497035980225, 2.7958717346191406, 2.4257779121398926,
             3.812797784805298, 3.0970330238342285, 2.963400363922119],
            [-19.262626650743186, -10.943594430573285, -26.829376625828445,
             -30.748585542663932, -5.418045138940215, -18.94312628544867]),
}

# sequence_flops at (prompt, outputs) (32, 1), (32, 16), (1024, 128), and
# kernel_cost at (batch 4, seq 32) and (batch 16, seq 1024).
COUNTS = {
    True: ([5021696, 8032256, 513359872], [(540672, 49152), (2149580800, 6291456)]),
    False: ([233621553152, 354643607552, 8854504996864],
            [(34603008, 2621440), (137573171200, 335544320)]),
}


@pytest.fixture(scope="module")
def dense():
    c = loader.load_cell(CELL, trace=False)
    return c.family, c.config


def test_dense_weights_match_the_parent(dense):
    fam, config = dense
    params = fam.make_params(fam.sizes(config, rehearse=True), SEED)
    got = {jax.tree_util.keystr(p): (hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
                                     + f" {x.dtype} {tuple(x.shape)}")
           for p, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == LEAVES


@pytest.mark.parametrize("quantize", [None, "fp8"])
def test_dense_reference_matches_the_parent(dense, quantize):
    fam, config = dense
    m = fam.sizes(config, rehearse=True)
    tokens = (np.arange(24, dtype=np.int32).reshape(2, 12) * 37 + 5) % m["vocab_size"]
    rows = np.array([[3, 7, 11], [0, 5, 10]], np.int32)
    ref = fam.reference_logits(m, SEED, tokens, rows, quantize=quantize)
    argmax, top, total = LOGITS[quantize]
    assert ref.shape == (2, 3, 256) and ref.dtype == np.float32
    assert ref.argmax(-1).tolist() == argmax
    np.testing.assert_allclose(ref.max(-1).ravel(), top, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref.astype(np.float64).sum(-1).ravel(), total,
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("rehearse", [True, False])
def test_dense_counts_match_the_parent(dense, rehearse):
    fam, config = dense
    m = fam.sizes(config, rehearse=rehearse)
    seq, kernel = COUNTS[rehearse]
    assert [fam.sequence_flops(m, p, n) for p, n in ((32, 1), (32, 16), (1024, 128))] == seq
    assert [fam.kernel_cost(m, batch=4, seq=32), fam.kernel_cost(m, batch=16, seq=1024)] == kernel
