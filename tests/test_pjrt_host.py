"""Native PJRT-C-API host: build, probe contract, bundle exporter contract.

The host has not been run on the chip on this installation
(docs/PJRT_HOST.md); these tests cover everything hermetic: the C++ host builds
against the in-image PJRT header, `probe` emits its one-line JSON contract
for a real plugin .so, and the bundle exporter's args.txt manifest matches
the exported program's input avals exactly (order, dtype, shape, weight
file sizes) — the contract the C host stages buffers by.
"""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
HOST = REPO / "native" / "pjrt_host"
LIBTPU = Path(sys.prefix) / "lib" / f"python{sys.version_info.major}.{sys.version_info.minor}" / "site-packages" / "libtpu" / "libtpu.so"


def _pjrt_header_available() -> bool:
    import sysconfig

    inc = Path(sysconfig.get_paths()["purelib"]) / "tensorflow" / "include"
    return (inc / "xla" / "pjrt" / "c" / "pjrt_c_api.h").exists()


@pytest.fixture(scope="module")
def host_binary():
    if not _pjrt_header_available():
        pytest.skip("PJRT C API header not in this image")
    r = subprocess.run(
        ["make", "pjrt_host"], cwd=REPO / "native", capture_output=True, text=True
    )
    assert r.returncode == 0, f"pjrt_host build failed:\n{r.stderr[-2000:]}"
    assert HOST.exists()
    return HOST


def test_usage_exit(host_binary):
    r = subprocess.run([str(host_binary)], capture_output=True, text=True)
    assert r.returncode == 2
    for verb in ("probe", "run", "serve", "stage"):
        assert verb in r.stderr


class TestStageContract:
    """`pjrt_host stage` is the hermetic half of the resident serve loop:
    it decodes a directory of JPEGs into the manifest's image-arg layout —
    the exact bytes `serve` hands BufferFromHostBuffer. Pinned here against
    the Python-side decode paths with no plugin and no TPU; the live serve
    transcript (real TPU, value parity, sustained img/s) is recorded in
    docs/PJRT_HOST.md."""

    @pytest.fixture(scope="class")
    def staged(self, host_binary, tmp_path_factory):
        import tiny_model  # noqa: F401

        from dmlc_tpu.models.pjrt_bundle import export_bundle

        out = tmp_path_factory.mktemp("bundle")
        export_bundle("tinynet", 8, out)
        raw = out / "staged.raw"
        photos = REPO / "tests" / "fixtures" / "photos"
        r = subprocess.run(
            [str(host_binary), "stage", str(out), "--dir", str(photos),
             "--out", str(raw)],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout), raw, photos

    def test_manifest_geometry_and_padding(self, staged):
        meta, raw, photos = staged
        n_photos = len(list(photos.glob("*.jpg")))
        assert meta["batch"] == 8 and meta["files"] == n_photos
        assert meta["padded"] == 8 - n_photos
        assert meta["decode_failures"] == 0
        assert raw.stat().st_size == meta["bytes"] == 8 * meta["size"] ** 2 * 3

    def test_bytes_match_native_decode_and_tile_padding(self, staged):
        """The staged bytes must be EXACTLY what the in-process decoder
        produces (same C code path as the ctypes binding) with the
        exporter's repeat-padding — so serve's device input is the same
        tensor the Python cluster path would stage for these files."""
        import numpy as np

        from dmlc_tpu import native

        if not native.available():
            pytest.skip("native decode library not built")
        meta, raw, photos = staged
        files = sorted(str(p) for p in photos.glob("*.jpg"))
        got = np.frombuffer(raw.read_bytes(), np.uint8).reshape(
            meta["batch"], meta["size"], meta["size"], 3
        )
        ref, status = native.decode_resize_batch(files, size=meta["size"])
        assert not status.any()
        np.testing.assert_array_equal(got[: len(files)], ref)
        reps = -(-meta["batch"] // len(files))
        np.testing.assert_array_equal(
            got[len(files):], np.tile(ref, (reps, 1, 1, 1))[len(files): meta["batch"]]
        )

    def test_bytes_near_pil_reference(self, staged):
        """Accuracy parity transfers: the staged pixels stay within the
        JPEG-noise tolerance of the PIL decode the torch-parity tests are
        built on (same bound ops/preprocess.load_batch documents)."""
        import numpy as np

        meta, raw, photos = staged
        files = sorted(str(p) for p in photos.glob("*.jpg"))
        got = np.frombuffer(raw.read_bytes(), np.uint8).reshape(
            meta["batch"], meta["size"], meta["size"], 3
        )[: len(files)]
        from dmlc_tpu.ops import preprocess as pp

        pil = pp.load_batch(files, size=meta["size"], backend="pil")
        diff = np.abs(got.astype(np.int32) - pil.astype(np.int32))
        assert diff.mean() < 0.5

    def test_stage_requires_dir_and_out(self, host_binary, tmp_path):
        r = subprocess.run(
            [str(host_binary), "stage", str(tmp_path)],
            capture_output=True, text=True,
        )
        assert r.returncode == 2 and "--dir" in r.stderr

    def test_stage_empty_dir_fails_loudly(self, host_binary, staged, tmp_path):
        meta, raw, _ = staged
        empty = tmp_path / "empty"
        empty.mkdir()
        r = subprocess.run(
            [str(host_binary), "stage", str(raw.parent), "--dir", str(empty),
             "--out", str(tmp_path / "x.raw")],
            capture_output=True, text=True,
        )
        assert r.returncode == 1 and "no JPEGs" in r.stderr


class TestServeRequestFraming:
    """`frame-check` runs the EXACT stdin framing serve's request loop
    uses (ReadRequestLine/SplitWhitespace) with no plugin and no TPU.
    Regression for the 64 KiB fgets truncation: a request line longer
    than the read buffer used to split into multiple bogus requests
    (with a mangled path at each seam) answered by multiple reply lines,
    desyncing the line-framed request/response contract."""

    def _frames(self, host_binary, payload: bytes):
        r = subprocess.run(
            [str(host_binary), "frame-check"],
            input=payload, capture_output=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return [json.loads(l) for l in r.stdout.decode().splitlines()]

    def test_long_request_line_is_one_request(self, host_binary):
        paths = [f"/data/corpus/img{i:06d}.jpg" for i in range(8000)]
        line = " ".join(paths)
        assert len(line) > 3 * 65536  # well past the old fgets buffer
        replies = self._frames(host_binary, (line + "\n").encode())
        assert len(replies) == 1
        assert replies[0]["paths"] == len(paths)

    def test_path_at_buffer_seam_not_mangled(self, host_binary):
        # One token straddling the 64 KiB boundary: under the old fgets
        # loop it split into two half-paths across two requests.
        a = "a" * 65530
        replies = self._frames(host_binary, f"{a} {'b' * 100}\n".encode())
        assert len(replies) == 1 and replies[0]["paths"] == 2

    def test_many_lines_map_one_to_one(self, host_binary):
        payload = b"x.jpg y.jpg\n\n   \nz.jpg\n"
        replies = self._frames(host_binary, payload)
        # Blank/whitespace lines produce no reply, like serve's loop.
        assert [r["paths"] for r in replies] == [2, 1]

    def test_final_unterminated_line_still_answers(self, host_binary):
        replies = self._frames(host_binary, b"x.jpg y.jpg")  # no trailing \n
        assert [r["paths"] for r in replies] == [2]


def test_probe_bad_plugin_reports_json(host_binary, tmp_path):
    bogus = tmp_path / "not_a_plugin.so"
    bogus.write_bytes(b"\x7fELF junk")
    r = subprocess.run(
        [str(host_binary), "probe", str(bogus)], capture_output=True, text=True
    )
    assert r.returncode == 0  # the report IS the product
    report = json.loads(r.stdout)
    assert report["loaded"] is False and report["error"]


def test_probe_libtpu_contract(host_binary):
    """libtpu.so ships in this image and exports GetPjrtApi: the probe must
    load it and report an API version. Client creation is allowed to fail
    (there is no chip where the tests run) but the probe must still emit
    valid JSON and exit 0."""
    if not LIBTPU.exists():
        pytest.skip("libtpu wheel not installed")
    r = subprocess.run(
        [str(host_binary), "probe", str(LIBTPU)],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode == 0
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["loaded"] is True
    major, minor = report["api_version"].split(".")
    assert int(major) >= 0 and int(minor) > 0
    assert "client_create" in report


class TestBundleExporter:
    @pytest.fixture(scope="class")
    def bundle(self, tmp_path_factory):
        import tiny_model  # noqa: F401  (registers tinynet)

        from dmlc_tpu.models.pjrt_bundle import export_bundle

        out = tmp_path_factory.mktemp("bundle")
        info = export_bundle("tinynet", 4, out)
        return out, info

    def test_layout_complete(self, bundle):
        out, info = bundle
        for name in ("program.mlir", "compile_options.pb", "args.txt"):
            assert (out / name).exists(), name
        # Client-create options are the operator's to supply, per plugin.
        assert not (out / "client_options.txt").exists()
        assert info["weight_args"] == info["inputs"] - 1

    def test_manifest_matches_exported_avals(self, bundle):
        """args.txt is the C host's staging contract: per-line dtype/shape
        must equal the exported program's in_avals in order, and every
        weight file must hold exactly shape*itemsize bytes."""
        out, _ = bundle
        import numpy as np

        from dmlc_tpu.models import export as export_lib

        blob = export_lib.export_serving("tinynet", batch_size=4)
        _, exported = export_lib.load_serving(blob)
        itemsize = {"u8": 1, "f32": 4, "i32": 4, "bf16": 2}
        lines = [
            l for l in (out / "args.txt").read_text().splitlines() if l.strip()
        ]
        assert len(lines) == len(exported.in_avals)
        for line, aval in zip(lines, exported.in_avals):
            spec, _, fname = line.partition("=")
            dt, _, dims = spec.partition(":")
            shape = tuple(int(d) for d in dims.split(",")) if dims else ()
            assert shape == tuple(aval.shape)
            if fname:
                want = int(np.prod(shape, dtype=np.int64)) * itemsize[dt]
                assert (out / fname).stat().st_size == want
        # Exactly one argument is the image batch (no weight file).
        assert sum(1 for l in lines if "=" not in l) == 1

    def test_program_is_stablehlo_with_weight_parameters(self, bundle):
        out, info = bundle
        text = (out / "program.mlir").read_text()
        assert "stablehlo" in text
        # Weights are parameters, not giant inlined constants: the module
        # stays small even though the weight files alongside are larger.
        weight_bytes = sum(
            (out / f).stat().st_size for f in ("args.txt",)
        ) + sum(p.stat().st_size for p in out.glob("arg*.raw"))
        assert info["program_bytes"] < max(200_000, weight_bytes)

    def test_image_staging(self, tmp_path):
        """--image decodes real JPEGs into the staged input batch: the
        manifest's image line references image.raw with exact batch bytes,
        padded by repetition to the export batch size."""
        import tiny_model  # noqa: F401

        from dmlc_tpu.models.pjrt_bundle import export_bundle

        photos = sorted(
            str(p) for p in (Path(__file__).parent / "fixtures" / "photos").glob("*.jpg")
        )
        out = tmp_path / "b"
        export_bundle("tinynet", 8, out, image_paths=photos[:3])  # pads 3 -> 8
        lines = (out / "args.txt").read_text().splitlines()
        image_lines = [l for l in lines if l.endswith("=image.raw")]
        assert len(image_lines) == 1
        dt, _, rest = image_lines[0].partition(":")
        dims = [int(d) for d in rest.split("=")[0].split(",")]
        assert dims[0] == 8 and dt == "u8"
        import numpy as np

        want = int(np.prod(dims))
        assert (out / "image.raw").stat().st_size == want
        raw = np.frombuffer((out / "image.raw").read_bytes(), np.uint8).reshape(dims)
        # Repetition padding: row 3 repeats row 0; real pixels, not zeros.
        np.testing.assert_array_equal(raw[3], raw[0])
        assert raw.std() > 10
        # Overflowing the batch fails loudly instead of dropping photos.
        with pytest.raises(ValueError, match="silently"):
            export_bundle("tinynet", 2, tmp_path / "b2", image_paths=photos[:3])

    def test_compile_options_deserializable(self, bundle):
        out, _ = bundle
        from jax._src.lib import xla_client

        data = (out / "compile_options.pb").read_bytes()
        assert len(data) > 0
        # Round-trips through the same serializer jax's compile path uses.
        assert xla_client.CompileOptions().SerializeAsString()[:4] == data[:4]


def test_makefile_clean_does_not_require_header():
    """`make clean` and the default native build stay independent of the
    PJRT header (only the pjrt_host target needs it)."""
    makefile = (REPO / "native" / "Makefile").read_text()
    assert "pjrt_host" in makefile
    assert shutil.which("g++")


def test_cli_export_bundle_verb(tmp_path):
    """The cluster CLI can produce the native host bundle (operator story:
    export from the REPL, serve with native/pjrt_host — no Python)."""
    import tiny_model  # noqa: F401

    from dmlc_tpu.cli import Cli

    class StubNode:
        class config:
            batch_size = 4

    out = Cli(StubNode()).run_command(f"export-bundle tinynet {tmp_path / 'b'}")
    assert "bundle for tinynet" in out and "pjrt_host serve" in out
    for name in ("program.mlir", "args.txt", "compile_options.pb"):
        assert (tmp_path / "b" / name).exists()
    assert "random-init" in out  # stub node has no SDFS weights
    # And the usage path answers cleanly.
    assert "usage:" in Cli(StubNode()).run_command("export-bundle tinynet")


def test_cli_export_bundle_uses_published_weights(tmp_path):
    """With weights published in SDFS, the verb bundles THOSE — the native
    host must serve what the cluster trained, not a random init."""
    import jax
    import numpy as np
    import tiny_model  # noqa: F401

    from dmlc_tpu.cli import Cli
    from dmlc_tpu.models import weights as weights_lib
    from dmlc_tpu.models.registry import get_model

    spec = get_model("tinynet")
    _, variables = spec.init_params(jax.random.PRNGKey(42))
    blob = weights_lib.weights_to_bytes("tinynet", variables)

    class StubSdfs:
        def get_bytes(self, name):
            assert name == weights_lib.sdfs_weights_name("tinynet")
            return 1, blob

    class StubNode:
        sdfs = StubSdfs()

        class config:
            batch_size = 4

    out = Cli(StubNode()).run_command(f"export-bundle tinynet {tmp_path / 'b'}")
    assert "published SDFS weights" in out
    # A bundled leaf matches the published tree, not seed-0 init.
    leaves = jax.tree_util.tree_leaves(variables)
    first = np.asarray(leaves[0])
    raw = np.frombuffer((tmp_path / "b" / "arg0.raw").read_bytes(), first.dtype)
    np.testing.assert_array_equal(raw, first.ravel())
