"""The programs of the four families that ``models/deepseek_v3`` did not
join are, byte for byte, what the parent of PR 37 lowered: the engine's step
and prefill of ``models/lm.TransformerFamily``, ``nemotron_h``, ``olmo_hybrid``
and ``lfm2_moe`` at their CPU presets, at two batch sizes, through the gather
path (``xla``) and through the fused attention kernel in the interpreter
(``kernel``: the text then holds the kernel's whole traced body, the DMA
pipeline the latent form now shares included). The digests are of
``as_text()`` on the parent commit (``python tests/lowered_programs.py``
there); a change that means to alter one of these programs prints the table
anew and says so. The kernel's Mosaic module at the cells' own shapes is
pinned in ``tests/test_tpu_compile.py`` (the one file that describes a chip).
"""

import pytest

pytest.importorskip("jax")

import lowered_programs  # noqa: E402

#: SHA-256 (16 hex digits) of ``as_text()``, parent of PR 37 (commit 37711bd).
PINS = {
    "xla": {
        "lm_small/4/step": "e8892ebec584d49e",
        "lm_small/4/prefill": "6cf512dbaf5b84a1",
        "lm_small/24/step": "31ce5fe05ba1eabf",
        "lm_small/24/prefill": "36c9f74a91abc48c",
        "nemotron_h_tiny/4/step": "05e64486111f7148",
        "nemotron_h_tiny/4/prefill": "023b045e0ca63ed5",
        "nemotron_h_tiny/24/step": "d48977557485aba7",
        "nemotron_h_tiny/24/prefill": "1e77c55890c38cb0",
        "olmo_hybrid_tiny/4/step": "78f6bd3127c89239",
        "olmo_hybrid_tiny/4/prefill": "ac8312944b0dac3e",
        "olmo_hybrid_tiny/24/step": "c1abd4b8d0b9ff16",
        "olmo_hybrid_tiny/24/prefill": "eb39254d1d789711",
        "lfm2_moe_tiny/4/step": "8b1ba2499e8e30c2",
        "lfm2_moe_tiny/4/prefill": "e4dd3ec204eb7e42",
        "lfm2_moe_tiny/24/step": "60093742fadd884a",
        "lfm2_moe_tiny/24/prefill": "d7e9c7555e9be45c",
    },
    "kernel": {
        "lm_small/4/step": "10f1668281bb1e17",
        "lm_small/4/prefill": "6cf512dbaf5b84a1",
        "lm_small/24/step": "ee0c078b8b22922f",
        "lm_small/24/prefill": "36c9f74a91abc48c",
        "nemotron_h_tiny/4/step": "82eb9d7a9695c905",
        "nemotron_h_tiny/4/prefill": "023b045e0ca63ed5",
        "nemotron_h_tiny/24/step": "93f0cb9866177da3",
        "nemotron_h_tiny/24/prefill": "1e77c55890c38cb0",
        "olmo_hybrid_tiny/4/step": "80ea77b0cee81fbc",
        "olmo_hybrid_tiny/4/prefill": "ac8312944b0dac3e",
        "olmo_hybrid_tiny/24/step": "d3bd61916b6c0075",
        "olmo_hybrid_tiny/24/prefill": "eb39254d1d789711",
        "lfm2_moe_tiny/4/step": "9e486a065753998e",
        "lfm2_moe_tiny/4/prefill": "e4dd3ec204eb7e42",
        "lfm2_moe_tiny/24/step": "0b6142ed92de0fa9",
        "lfm2_moe_tiny/24/prefill": "d7e9c7555e9be45c",
    },
}


@pytest.mark.parametrize("path", sorted(PINS))
@pytest.mark.parametrize("model,slots", lowered_programs.CASES)
def test_step_and_prefill_lower_to_what_the_parent_lowered(model, slots, path):
    texts = lowered_programs.program_texts(model, slots, use_pallas=path == "kernel")
    got = {f"{model}/{slots}/{name}": lowered_programs.sha(text) for name, text in texts.items()}
    assert got == {key: PINS[path][key] for key in got}


def test_the_pins_cover_both_programs_of_every_case():
    want = {f"{model}/{slots}/{name}" for model, slots in lowered_programs.CASES
            for name in ("step", "prefill")}
    assert set(PINS["xla"]) == set(PINS["kernel"]) == want
    # The kernel is in the step alone: a prefill's text does not depend on the path.
    for key in want:
        assert (PINS["xla"][key] == PINS["kernel"][key]) == key.endswith("prefill")
