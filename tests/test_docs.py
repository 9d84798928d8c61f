"""Docs gate: markdown cross-references must resolve, and the documented
entry points the docs name must actually exist.

Scans README.md, docs/*.md and results/README.md for relative markdown
links and asserts every target exists (so docs/BITPLANE_FORMAT.md and
docs/ARCHITECTURE.md cross-references can't rot).  Also pins the
README -> docs links the PR-4 acceptance criteria require, and checks
that code identifiers the format spec declares as producers/consumers are
importable.  CI runs this alongside ``pytest --doctest-modules`` over
``planes.py`` as the docs step.
"""
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LINK = re.compile(r"(?<!!)\[[^\]]+\]\(([^)\s]+)\)")  # [text](target), not images


def _doc_files():
    files = [os.path.join(REPO, "README.md"),
             os.path.join(REPO, "results", "README.md")]
    docs = os.path.join(REPO, "docs")
    for name in sorted(os.listdir(docs)):
        if name.endswith(".md"):
            files.append(os.path.join(docs, name))
    return files


def _relative_links(path):
    with open(path) as f:
        text = f.read()
    # strip fenced code blocks: bash snippets aren't hyperlinks
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    for m in _LINK.finditer(text):
        target = m.group(1)
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        yield target.split("#", 1)[0]


@pytest.mark.parametrize("doc", _doc_files(),
                         ids=lambda p: os.path.relpath(p, REPO))
def test_markdown_relative_links_resolve(doc):
    base = os.path.dirname(doc)
    missing = [t for t in _relative_links(doc)
               if not os.path.exists(os.path.join(base, t))]
    assert not missing, f"{os.path.relpath(doc, REPO)} has dead links: {missing}"


def test_readme_links_required_docs():
    """The acceptance criteria: both specs exist AND are linked from README."""
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    for target in ("docs/ARCHITECTURE.md", "docs/BITPLANE_FORMAT.md"):
        assert os.path.exists(os.path.join(REPO, target)), target
        assert target in readme, f"README does not link {target}"


def test_format_spec_names_real_code():
    """docs/BITPLANE_FORMAT.md's producer/consumer table must not rot."""
    from repro.core.threeway import _threeway_program  # noqa: F401
    from repro.core.twoway import _twoway_program  # noqa: F401
    from repro.kernels.czek3.kernel import threeway_batch_levels_pallas  # noqa: F401
    from repro.kernels.mgemm_levels import (  # noqa: F401
        PackedPlanes,
        decode_bitplanes,
        encode_bitplanes,
        encode_bitplanes_np,
        pad_planes,
        shard_planes_fields,
        slice_planes_vectors,
        values_from_planes,
    )
    from repro.kernels.mgemm_levels.kernel import (  # noqa: F401
        _plane_matmuls,
        _unpack_plane_tile,
    )
    # the binary fast path the format spec's "Binary fast path" note names
    from repro.kernels.mgemm_levels import POPCOUNT  # noqa: F401
    from repro.kernels.popgemm import (  # noqa: F401
        metric2_pop,
        pop_planes,
        threeway_batch_pop,
    )
    from repro.kernels.popgemm.kernel import (  # noqa: F401
        _pack_words,
        _pop_contract,
    )


def test_store_spec_names_real_code():
    """The "On-disk storage" chapter's named entry points must exist, and
    the spec constants it documents must match the code."""
    from repro.store import (  # noqa: F401
        DatasetReader,
        FORMAT_NAME,
        FORMAT_VERSION,
        MANIFEST_NAME,
        bed_paths,
        read_bed,
        read_manifest,
        validate_leveled,
        write_dataset,
    )

    assert FORMAT_NAME == "repro-bitplane-dataset"
    assert MANIFEST_NAME == "dataset.json"
    # the dataset CLI the README quickstart drives
    from repro.launch.dataset import main  # noqa: F401

    with open(os.path.join(REPO, "docs", "BITPLANE_FORMAT.md")) as f:
        spec = f.read()
    for name in ("On-disk storage", "dataset.json", "stats.npy",
                 "shard_planes_fields", "pad_planes", "sha256",
                 "Missing-genotype"):
        assert name in spec, f"BITPLANE_FORMAT.md lost its {name!r} section"
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    assert "repro.launch.dataset" in readme, "README lost the dataset quickstart"
    assert "--dataset" in readme


def test_append_delta_docs_name_real_code():
    """The "Append & delta" chapter (BITPLANE_FORMAT.md) and the serving /
    delta sections (ARCHITECTURE.md) must name code that exists."""
    from repro.api.engine import SimilarityEngine
    from repro.core.delta import (  # noqa: F401
        delta_accounting,
        merge_delta,
        packed_upper_index,
        twoway_delta,
    )
    from repro.core.twoway import _cached_jit  # noqa: F401
    from repro.serve.engine import SimilarityService, _payload_hash  # noqa: F401
    from repro.store import append_dataset  # noqa: F401
    from repro.stream import stream_twoway_delta  # noqa: F401

    assert hasattr(SimilarityEngine, "run_delta")
    for attr in ("submit_async", "submit", "warmup", "shutdown"):
        assert hasattr(SimilarityService, attr), attr

    with open(os.path.join(REPO, "docs", "BITPLANE_FORMAT.md")) as f:
        spec = f.read()
    for name in ("Append & delta", "append_dataset", "dataset_version",
                 "parent", "merge_delta", "packed_upper_index",
                 "ring_payload_bytes = 0"):
        assert name in spec, f"BITPLANE_FORMAT.md lost its {name!r} mention"
    with open(os.path.join(REPO, "docs", "ARCHITECTURE.md")) as f:
        arch = f.read()
    for name in ("Delta campaigns", "Serving layer", "SimilarityService",
                 "submit_async", "run_delta", "delta_from", "warmup",
                 "delta_hits", "stream_twoway_delta"):
        assert name in arch, f"ARCHITECTURE.md lost its {name!r} mention"
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    assert "--delta-from" in readme, "README lost the delta quickstart"
    assert "append" in readme


def test_architecture_path_matrix_matches_executor():
    """The fallback matrix documented in docs/ARCHITECTURE.md is the one
    the executor implements (spot-check the load-bearing rows)."""
    from repro.core.tile_executor import TileExecutor
    from repro.core.twoway import CometConfig

    rows3 = {  # (impl, encoding) -> documented path3
        ("levels", "bitplane"): "fused-levels-ring",
        ("levels", "none"): "fused-levels",
        ("pallas", "none"): "fused-vpu",
        ("levels_xla", "bitplane"): "unfused",
        ("xla", "none"): "unfused",
    }
    for (impl, enc), want in rows3.items():
        ex = TileExecutor(cfg=CometConfig(impl=impl, encoding=enc))
        assert ex.path3 == want, (impl, enc, ex.path3)
    # n_pf > 1 keeps the fused MXU path: raw in-kernel partials, psummed
    # over "pf", assembled by the merge epilogue out of kernel
    ex = TileExecutor(cfg=CometConfig(impl="levels", n_pf=2))
    assert ex.path == "fused-levels" and "merge epilogue" in ex.path_reason
    # streamed campaigns defer every flush to the cross-shard merge
    ex = TileExecutor(cfg=CometConfig(impl="levels", encoding="bitplane"),
                      deferred=True)
    assert ex.path == "streamed-fused-levels"
    assert ex.path3 == "streamed-fused-levels-ring"
    # binary fast path: levels == 1 swaps the plane-dot kernels for the
    # popcount bit-GEMM at every decision site (same conditions otherwise)
    ex = TileExecutor(cfg=CometConfig(impl="levels", levels=1,
                                      encoding="bitplane"))
    assert ex.path == "fused-popcount"
    assert ex.path3 == "fused-popcount-ring"
    ex = TileExecutor(cfg=CometConfig(impl="levels", levels=1,
                                      encoding="none"))
    assert ex.path3 == "fused-popcount"
    ex = TileExecutor(cfg=CometConfig(impl="levels", levels=1, n_pf=2))
    assert ex.path == "fused-popcount" and "merge epilogue" in ex.path_reason
    ex = TileExecutor(cfg=CometConfig(impl="levels", levels=1,
                                      encoding="bitplane"), deferred=True)
    assert ex.path == "streamed-fused-popcount"
    assert ex.path3 == "streamed-fused-popcount-ring"
    # levels_xla keeps the unfused plane contraction even for binary data
    ex = TileExecutor(cfg=CometConfig(impl="levels_xla", levels=1,
                                      encoding="bitplane"))
    assert ex.path == "unfused" and ex.path3 == "unfused"


# -- the result meta schema gate ---------------------------------------------


def _parse_meta_schema():
    """Parse the "## Result `meta` schema" bullets into
    ``{block: (required, optional)}`` key sets."""
    with open(os.path.join(REPO, "docs", "ARCHITECTURE.md")) as f:
        arch = f.read()
    assert "## Result `meta` schema" in arch, \
        "ARCHITECTURE.md lost the meta schema section"
    sec = arch.split("## Result `meta` schema", 1)[1].split("\n## ", 1)[0]
    blocks = {}
    for m in re.finditer(
        r"- `(\w+)` \([^)]*\): required\s+([^;.]*)(?:;\s*optional\s+([^.]*))?\.",
        sec, flags=re.S,
    ):
        name, req, opt = m.group(1), m.group(2), m.group(3) or ""
        blocks[name] = (set(re.findall(r"`(\w+)`", req)),
                        set(re.findall(r"`(\w+)`", opt)))
    return blocks


def _assert_meta_documented(meta, blocks, where):
    undocumented = set(meta) - set(blocks)
    assert not undocumented, f"{where}: undocumented meta blocks {undocumented}"
    for key, block in meta.items():
        required, optional = blocks[key]
        got = set(block)
        missing = required - got
        assert not missing, f"{where}: meta[{key!r}] missing required {missing}"
        extra = got - required - optional
        assert not extra, f"{where}: meta[{key!r}] emits undocumented {extra}"


def test_meta_schema_matches_emitted(tmp_path):
    """The documented schema IS what real campaigns emit: every block a
    campaign attaches is documented, required keys are always present,
    and no campaign emits a key the docs don't list — checked across the
    in-memory, streamed, delta, batched, and traced forms."""
    from repro.api import InputSpec, SimilarityEngine, SimilarityRequest
    from repro.core.synthetic import random_integer_vectors
    from repro.obs import trace
    from repro.store import append_dataset, write_dataset

    blocks = _parse_meta_schema()
    assert set(blocks) == {"obs", "dataset", "stream", "delta", "batch"}

    engine = SimilarityEngine()
    V = random_integer_vectors(32, 10, max_value=2, seed=1)
    path = os.path.join(str(tmp_path), "ds")
    write_dataset(path, V, levels=2, n_shards=2)
    sreq = SimilarityRequest(
        way=2, metric="czekanowski", impl="levels", levels=2,
        streaming="on", max_host_bytes=400,
        input=InputSpec(source="planes", path=path),
    )

    plain = engine.run(SimilarityRequest(way=2, metric="czekanowski"), V)
    assert set(plain.meta) == {"obs"}
    _assert_meta_documented(plain.meta, blocks, "in-memory")

    streamed = engine.run(sreq)
    assert {"obs", "dataset", "stream"} <= set(streamed.meta)
    _assert_meta_documented(streamed.meta, blocks, "streamed")

    append_dataset(path, random_integer_vectors(32, 4, max_value=2, seed=2))
    delta = engine.run_delta(sreq, streamed)
    assert "delta" in delta.meta
    _assert_meta_documented(delta.meta, blocks, "delta")

    trace.enable()
    try:
        batched = engine.run(SimilarityRequest(
            way=2, metric="czekanowski", metrics=("sorenson",),
            impl="levels", levels=2, encoding="bitplane"), V)
    finally:
        trace.disable()
    assert "batch" in batched.meta
    # the traced run exercises the OPTIONAL obs key (phases)
    assert "phases" in batched.meta["obs"]
    _assert_meta_documented(batched.meta, blocks, "batched+traced")
    for mname, sname, res in batched.campaigns:
        _assert_meta_documented(res.meta, blocks, f"campaign {mname}/{sname}")


def test_observability_docs_name_real_code():
    """docs/OBSERVABILITY.md exists, is linked from README, and the API +
    CLI flags it documents are real."""
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    for name in ("enable", "disable", "enabled", "span",
                 "format_phase_table", "validate_chrome_trace",
                 "CANONICAL_PHASES", "PROFILER_PREFIX", "Tracer"):
        assert hasattr(obs_trace, name), name
    for name in ("Counter", "Gauge", "Histogram", "MetricsRegistry",
                 "default_registry", "count_jit_events", "jit_counts"):
        assert hasattr(obs_metrics, name), name
    from repro.serve.engine import SimilarityService
    for attr in ("stats", "metrics"):
        assert hasattr(SimilarityService, attr), attr

    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    assert "docs/OBSERVABILITY.md" in readme, "README does not link the doc"
    assert "--trace" in readme
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    for name in ("--trace", "--metrics-json", "prefetch-stage", "ring-step",
                 "dispatch", "readback", "entries", "hash",
                 "validate_chrome_trace", "jax.profiler.trace", "repro.",
                 "jit.lowerings", "jit.compiles", "device_roofline",
                 "stall_seconds", "MetricsRegistry"):
        assert name in doc, f"OBSERVABILITY.md lost its {name!r} mention"
    # the CLI flags the doc quotes exist in the launchers' parsers
    with open(os.path.join(REPO, "src", "repro", "launch",
                           "similarity.py")) as f:
        assert "--trace" in f.read()
    with open(os.path.join(REPO, "src", "repro", "launch", "serve.py")) as f:
        assert "--metrics-json" in f.read()
