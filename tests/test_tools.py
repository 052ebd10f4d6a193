"""User tooling (reference python/paddle/utils/): log curve plotting, model
diagram emission, torch parameter import."""

import os

import numpy as np
import pytest
import jax

import paddle_tpu.layers as L
from paddle_tpu.layers.graph import Topology, reset_names


def test_plotcurve_parses_and_writes(tmp_path):
    from paddle_tpu.utils.tools import plotcurve
    log = [
        "I 0729 paddle_tpu] Pass 0 done, mean cost 0.83612 Eval: err=0.5\n",
        "I 0729 paddle_tpu] Pass 1 done, mean cost 0.51 Eval: err=0.25\n",
        "I 0729 paddle_tpu] Pass 2 done, mean cost 0.20 Eval: err=0.125\n",
    ]
    out = tmp_path / "curve.png"
    data = plotcurve.plot_curves(log, str(out), keys=("cost", "err"))
    assert out.exists() and out.stat().st_size > 0
    assert data["cost"] == [(0, 0.83612), (1, 0.51), (2, 0.20)]
    assert data["err"] == [(0, 0.5), (1, 0.25), (2, 0.125)]


def test_make_diagram_dot(tmp_path):
    from paddle_tpu.utils.tools import make_diagram, topology_dot
    reset_names()
    x = L.data_layer("x", size=4)
    out = L.fc_layer(x, size=2, act="softmax", name="out")
    dot = topology_dot(out)
    assert '"x" -> "out"' in dot and "digraph" in dot
    p = make_diagram(out, str(tmp_path / "m.dot"))
    assert open(p).read().startswith("digraph")


def test_torch_import_positional_and_mapped():
    torch = pytest.importorskip("torch")
    from paddle_tpu.utils.tools import from_torch_state_dict
    reset_names()
    x = L.data_layer("x", size=4)
    out = L.fc_layer(x, size=3, act=None, name="fc")
    topo = Topology([out])
    params = topo.init(jax.random.PRNGKey(0))

    lin = torch.nn.Linear(4, 3)
    sd = lin.state_dict()            # weight [3,4], bias [3]
    # positional: [w, b] order matches our {'fc': {'w', 'b'}} leaves
    got = from_torch_state_dict(params, sd)
    np.testing.assert_allclose(np.asarray(got["fc"]["w0"]),
                               sd["weight"].numpy().T, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got["fc"]["b"]),
                               sd["bias"].numpy(), rtol=1e-6)

    got2 = from_torch_state_dict(params, sd,
                                 mapping={"fc/w0": "weight", "fc/b": "bias"})
    np.testing.assert_allclose(np.asarray(got2["fc"]["w0"]),
                               sd["weight"].numpy().T, rtol=1e-6)

    # model still runs with imported weights
    val = topo.apply(got, {"x": np.ones((2, 4), np.float32)}, mode="test")
    ref = lin(torch.ones(2, 4)).detach().numpy()
    np.testing.assert_allclose(np.asarray(val), ref, rtol=1e-5, atol=1e-6)


def test_preprocess_img_roundtrip(tmp_path):
    PIL = pytest.importorskip("PIL")
    from PIL import Image
    from paddle_tpu.utils.tools import preprocess_img
    from paddle_tpu import native
    if not native.is_available():
        pytest.skip("native runtime not built")
    src = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (src / cls).mkdir(parents=True)
        for i in range(4):
            arr = (np.random.RandomState(i).rand(20, 30, 3) * 255
                   ).astype(np.uint8)
            Image.fromarray(arr).save(src / cls / f"{i}.png")
    out = tmp_path / "rec"
    counts, mean = preprocess_img.preprocess(str(src), str(out), size=16,
                                             test_ratio=0.25, seed=0)
    assert counts["train"] + counts["test"] == 8
    rows = list(preprocess_img.record_reader(
        str(out / "train.rec"), str(out / "meta.npz"))())
    assert len(rows) == counts["train"]
    x, y = rows[0]
    assert x.shape == (16 * 16 * 3,) and y in (0, 1)
    assert np.isfinite(x).all()


def test_v2_ploter(tmp_path, monkeypatch):
    """paddle.v2.plot.Ploter (reference v2/plot/plot.py): append named
    curves, plot to a file headless, DISABLE_PLOT short-circuits."""
    from paddle_tpu.v2.plot import Ploter
    p = Ploter("train_cost", "test_cost")
    for i in range(5):
        p.append("train_cost", i, 1.0 / (i + 1))
        p.append("test_cost", i, 1.2 / (i + 1))
    out = tmp_path / "curves.png"
    p.plot(path=str(out))
    assert out.exists() and out.stat().st_size > 0
    monkeypatch.setenv("DISABLE_PLOT", "True")
    p.plot()          # prints instead of plotting; no error
    p.reset()
    assert not p.__plot_data__["train_cost"].step


def test_xprof_report_attributes_categories(tmp_path):
    """End-to-end: capture a real jax.profiler trace of a jitted matmul
    loop, then the report must attribute the bulk to matmul_conv and
    expose busy/idle per track (the pre-staged MFU analysis loop)."""
    import json as _json
    import jax
    import jax.numpy as jnp
    from paddle_tpu.scripts import xprof_report

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    prof = str(tmp_path / "prof")
    jax.profiler.start_trace(prof)
    for _ in range(4):
        f(x).block_until_ready()
    jax.profiler.stop_trace()

    runs = xprof_report.find_runs(prof)
    assert len(runs) == 1
    rep = xprof_report.report_run(runs[0])
    assert rep["tracks"], "no device/host tracks found"
    track = next(iter(rep["tracks"].values()))
    assert track["wall_us"] > 0 and 0 <= track["idle_pct"] <= 100
    cats = track["by_category_us"]
    assert cats.get("matmul_conv", 0) > 0
    assert cats["matmul_conv"] >= max(cats.values()) * 0.5
    # text + json renderers both work
    assert "matmul_conv" in xprof_report.render(rep)
    rc = xprof_report.main([prof, "--json"])
    assert rc == 0
    # --write: both artifacts in one parse
    rc = xprof_report.main([prof, "--write", str(tmp_path / "rep")])
    assert rc == 0
    assert (tmp_path / "rep.json").exists()
    assert "matmul_conv" in (tmp_path / "rep.txt").read_text()
    # categorization traps fixed by review: convert is NOT MXU time,
    # custom-call (Pallas kernels) gets its own bucket
    assert xprof_report.categorize("convert.5") == "fusion_elementwise"
    assert xprof_report.categorize("custom-call.7") == "custom_kernel"
    assert xprof_report.categorize("convolution.3") == "matmul_conv"
    assert xprof_report.categorize("while.2") == "scan_control"


def test_ref_params_roundtrip(tmp_path):
    """Reference binary Parameter format (paraconvert.py:33-55 spec):
    write -> read identity, binary<->text round trip, 16-byte header."""
    import struct
    from paddle_tpu.utils.tools import ref_params
    rng = np.random.RandomState(0)
    table = rng.randn(7, 5).astype(np.float32)
    b = tmp_path / "emb.bin"
    ref_params.write_param(str(b), table)
    # header layout is the documented 16 bytes: version, float_size, count
    raw = b.read_bytes()
    version, fsize, count = struct.unpack("<iiq", raw[:16])
    assert (version, fsize, count) == (0, 4, 35)
    np.testing.assert_array_equal(
        ref_params.read_param(str(b)).reshape(7, 5), table)
    # binary -> text -> binary survives (text carries 7 decimals)
    t = tmp_path / "emb.txt"
    b2 = tmp_path / "emb2.bin"
    assert ref_params.binary2text(str(b), str(t), dim=5) == 7
    assert t.read_text().splitlines()[0] == "0,4,35"
    ref_params.text2binary(str(t), str(b2))
    np.testing.assert_allclose(ref_params.read_param(str(b2)),
                               table.reshape(-1), atol=1e-6)


def test_ref_params_f64_and_errors(tmp_path):
    import struct
    from paddle_tpu.utils.tools import ref_params
    # f64 body (float_size=8) reads too
    vals = np.arange(6, dtype=np.float64)
    p = tmp_path / "d.bin"
    with open(p, "wb") as f:
        f.write(struct.pack("<iiq", 0, 8, 6))
        vals.tofile(f)
    got = ref_params.read_param(str(p))
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got, vals)
    # truncated body fails loudly
    q = tmp_path / "t.bin"
    q.write_bytes(struct.pack("<iiq", 0, 4, 100) + b"\x00" * 8)
    with pytest.raises(ValueError, match="promises 100"):
        ref_params.read_param(str(q))
    # junk float_size fails loudly
    r = tmp_path / "j.bin"
    r.write_bytes(struct.pack("<iiq", 0, 3, 1) + b"\x00" * 4)
    with pytest.raises(ValueError, match="float_size"):
        ref_params.read_param(str(r))


def test_ref_params_extract_and_pass_dir(tmp_path):
    """extract_para.py role (sub-dict rows) + reference pass-dir bulk
    load feeding an actual embedding_layer lookup."""
    from paddle_tpu.utils.tools import ref_params
    rng = np.random.RandomState(1)
    table = rng.randn(20, 4).astype(np.float32)
    emb = tmp_path / "baidu_emb.bin"
    ref_params.write_param(str(emb), table)
    rows = ref_params.extract_rows(str(emb), [3, 0, 19], 4)
    np.testing.assert_array_equal(rows, table[[3, 0, 19]])
    with pytest.raises(ValueError, match="rows"):
        ref_params.extract_rows(str(emb), [20], 4)

    # reference checkpoint dir: one binary file per param + a done marker
    d = tmp_path / "pass-00003"
    d.mkdir()
    ref_params.write_param(str(d / "emb.w0"), table)
    ref_params.write_param(str(d / "fc.w0"), table[:4, :2])
    (d / "done").write_text("")
    loaded = ref_params.load_pass_dir(str(d))
    assert sorted(loaded) == ["emb.w0", "fc.w0"]
    np.testing.assert_array_equal(loaded["emb.w0"].reshape(20, 4), table)

    # the imported table drives a real embedding lookup
    import jax.numpy as jnp
    from paddle_tpu.ops.embedding import embedding_lookup
    out = embedding_lookup(jnp.asarray(loaded["emb.w0"].reshape(20, 4)),
                           jnp.asarray([[3, 0]]))
    np.testing.assert_allclose(np.asarray(out)[0], table[[3, 0]],
                               atol=1e-6)


def test_ref_embedding_demo_cli(tmp_path):
    """demo CLI: ref_embedding subcommand extracts a sub-dict from a
    pretrained-format table (the pre_DictAndModel.sh -> extract_para.py
    workflow, zero-egress)."""
    import subprocess
    import sys as _sys
    from paddle_tpu.utils.tools import ref_params
    rng = np.random.RandomState(2)
    table = rng.randn(11, 3).astype(np.float32)
    emb = tmp_path / "model.bin"
    ref_params.write_param(str(emb), table)
    idx = tmp_path / "ids.txt"
    idx.write_text("5\n1\n9\n")
    demo = os.path.join(os.path.dirname(__file__), "..", "demo",
                        "model_zoo", "extract_features.py")
    r = subprocess.run(
        [_sys.executable, demo, "ref_embedding", "--emb_file", str(emb),
         "--dim", "3", "--indices", str(idx),
         "--out", str(tmp_path / "sub.npz"),
         "--text", str(tmp_path / "sub.txt")],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    got = np.load(tmp_path / "sub.npz")["embedding"]
    np.testing.assert_array_equal(got, table[[5, 1, 9]])
    assert (tmp_path / "sub.txt").read_text().splitlines()[0] == "3 3"
