"""``span_attr_mean`` times ``scale``: an attribute counted in bytes read in MB."""
from benchlib import manifest


def read(ctx, name: str, attr: str, scale: float):
    mean = manifest.plugin("readers", "span_attr_mean").read(ctx, name=name, attr=attr)
    return None if mean is None else scale * mean
