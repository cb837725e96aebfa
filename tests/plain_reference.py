"""The benchmark's plain references, for the tier-1 tests that pin a model
family to one: ``benchmark/reference/<stem>.py`` loaded by path (the same
copy of the plain math decides ``correct`` on the chip), and a variables tree
as the flat ``{path: leaf}`` dict a reference reads. Both are ``chip_smoke``'s,
which compares a family with its reference on the chip too."""

from pathlib import Path

from chip_smoke import flat_of, plain_reference as load  # noqa: F401

REPO = Path(__file__).resolve().parents[1]
