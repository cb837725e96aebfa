"""The engine's programs of every family, byte for byte, as pinned: the step
and prefill of ``models/lm.TransformerFamily``, ``nemotron_h``, ``olmo_hybrid``
and ``lfm2_moe`` as commit 37711bd lowered them, ``deepseek_v3`` as commit
0f7afdd did, and ``granite_hybrid`` as it was added (the Mamba-2 mixer it
shares with ``nemotron_h`` moved to ``models/mamba2`` with it, and
``nemotron_h``'s pins held), at their CPU presets, at two batch sizes,
through the gather path (``xla``) and through the fused attention kernel in
the interpreter (``kernel``: the text then holds the kernel's whole traced
body, the DMA pipeline the latent form shares included). The digests are of
``as_text()`` on the commit named (``python tests/lowered_programs.py``
there); a change that means to alter one of these programs prints the table
anew and says so. The kernel's Mosaic module at the cells' own shapes is
pinned in ``tests/test_tpu_compile.py`` (the one file that describes a chip).

The three expert families' steps at 4 slots are the programs at these
presets whose expert layer takes the grouped form (4 rows: ``t k < 2
n_experts``); their pins are those of the grouped matmul kernel
(``ops/grouped_matmul``) that replaced ``jax.lax.ragged_dot`` there. Every
other pin, the prefills at 32 rows (the dense form) and every step at 24
slots (the dense form, as every cell's 64-slot step) among them, is as the
commits above lowered it.
"""

import pytest

pytest.importorskip("jax")

import lowered_programs  # noqa: E402

#: SHA-256 (16 hex digits) of ``as_text()``: commit 37711bd unless marked.
PINS = {
    "xla": {
        "lm_small/4/step": "e8892ebec584d49e",
        "lm_small/4/prefill": "6cf512dbaf5b84a1",
        "lm_small/24/step": "31ce5fe05ba1eabf",
        "lm_small/24/prefill": "36c9f74a91abc48c",
        "nemotron_h_tiny/4/step": "abc490f3457fce56",  # grouped form: the grouped matmul kernel
        "nemotron_h_tiny/4/prefill": "023b045e0ca63ed5",
        "nemotron_h_tiny/24/step": "d48977557485aba7",
        "nemotron_h_tiny/24/prefill": "1e77c55890c38cb0",
        "olmo_hybrid_tiny/4/step": "78f6bd3127c89239",
        "olmo_hybrid_tiny/4/prefill": "ac8312944b0dac3e",
        "olmo_hybrid_tiny/24/step": "c1abd4b8d0b9ff16",
        "olmo_hybrid_tiny/24/prefill": "eb39254d1d789711",
        "lfm2_moe_tiny/4/step": "a1c4288c8b139e3d",  # grouped form: the grouped matmul kernel
        "lfm2_moe_tiny/4/prefill": "e4dd3ec204eb7e42",
        "lfm2_moe_tiny/24/step": "60093742fadd884a",
        "lfm2_moe_tiny/24/prefill": "d7e9c7555e9be45c",
        # commit 0f7afdd: the fifth family, unpinned before
        "deepseek_v3_tiny/4/step": "7ddca2f8aa8ca9df",  # grouped form: the grouped matmul kernel
        "deepseek_v3_tiny/4/prefill": "69f7a10ef36d20ae",
        "deepseek_v3_tiny/24/step": "604500b85b7e4374",
        "deepseek_v3_tiny/24/prefill": "0d8083f8212f667c",
        # the sixth family, as added
        "granite_hybrid_tiny/4/step": "d2189d67156d8ae1",
        "granite_hybrid_tiny/4/prefill": "4d8dae9410dc2c6c",
        "granite_hybrid_tiny/24/step": "11cb5678e045f061",
        "granite_hybrid_tiny/24/prefill": "916a38c35b6bc648",
    },
    "kernel": {
        "lm_small/4/step": "10f1668281bb1e17",
        "lm_small/4/prefill": "6cf512dbaf5b84a1",
        "lm_small/24/step": "ee0c078b8b22922f",
        "lm_small/24/prefill": "36c9f74a91abc48c",
        "nemotron_h_tiny/4/step": "f25502978303942d",  # grouped form: the grouped matmul kernel
        "nemotron_h_tiny/4/prefill": "023b045e0ca63ed5",
        "nemotron_h_tiny/24/step": "93f0cb9866177da3",
        "nemotron_h_tiny/24/prefill": "1e77c55890c38cb0",
        "olmo_hybrid_tiny/4/step": "80ea77b0cee81fbc",
        "olmo_hybrid_tiny/4/prefill": "ac8312944b0dac3e",
        "olmo_hybrid_tiny/24/step": "d3bd61916b6c0075",
        "olmo_hybrid_tiny/24/prefill": "eb39254d1d789711",
        "lfm2_moe_tiny/4/step": "0f407e3475804745",  # grouped form: the grouped matmul kernel
        "lfm2_moe_tiny/4/prefill": "e4dd3ec204eb7e42",
        "lfm2_moe_tiny/24/step": "0b6142ed92de0fa9",
        "lfm2_moe_tiny/24/prefill": "d7e9c7555e9be45c",
        # commit 0f7afdd: the fifth family, unpinned before
        "deepseek_v3_tiny/4/step": "4b5c9ce6b5a280eb",  # grouped form: the grouped matmul kernel
        "deepseek_v3_tiny/4/prefill": "69f7a10ef36d20ae",
        "deepseek_v3_tiny/24/step": "36ca592c09fd3b2c",
        "deepseek_v3_tiny/24/prefill": "0d8083f8212f667c",
        # the sixth family, as added
        "granite_hybrid_tiny/4/step": "74c8b78cd03000f3",
        "granite_hybrid_tiny/4/prefill": "4d8dae9410dc2c6c",
        "granite_hybrid_tiny/24/step": "76b9242a32209cb0",
        "granite_hybrid_tiny/24/prefill": "916a38c35b6bc648",
    },
}


@pytest.mark.parametrize("path", sorted(PINS))
@pytest.mark.parametrize("model,slots", lowered_programs.CASES)
def test_step_and_prefill_lower_to_what_the_parent_lowered(model, slots, path):
    texts = lowered_programs.program_texts(model, slots, use_pallas=path == "kernel")
    got = {f"{model}/{slots}/{name}": lowered_programs.sha(text) for name, text in texts.items()}
    assert got == {key: PINS[path][key] for key in got}


#: The scopes each family's mixers and feed-forwards run under: the pins hash
#: the text without them, and the benchmark's per-layer readers (and
#: docs/OBSERVABILITY.md) name them. ``deepseek_v3`` has its own test beside
#: its latent kernel's (``tests/test_deepseek_v3.py``).
SCOPES = {
    "nemotron_h_tiny": ("mamba", "attn", "moe"),
    "olmo_hybrid_tiny": ("deltanet", "attn", "mlp"),
    "lfm2_moe_tiny": ("shortconv", "attn", "moe", "mlp"),
    "granite_hybrid_tiny": ("mamba", "attn", "mlp"),
}


@pytest.mark.parametrize("model", sorted(SCOPES))
def test_named_scopes_are_in_both_programs(model):
    for name, program in lowered_programs.lowered(model, 4, use_pallas=False).items():
        text = program.as_text(debug_info=True)
        for scope in SCOPES[model]:
            assert f"/{scope}/" in text, (name, scope)


def test_the_pins_cover_both_programs_of_every_case():
    want = {f"{model}/{slots}/{name}" for model, slots in lowered_programs.CASES
            for name in ("step", "prefill")}
    assert set(PINS["xla"]) == set(PINS["kernel"]) == want
    # The kernel is in the step alone: a prefill's text does not depend on the path.
    for key in want:
        assert (PINS["xla"][key] == PINS["kernel"][key]) == key.endswith("prefill")
