"""What every architecture's weights share: ramp sites, the padded
vocabulary, the key drawn from the seed, and the check that the
program stores its parameters in the layout the benchmark draws.

The layout and the draw themselves belong to the architecture the
configuration names (``arch/<architecture>.py``).
"""
from __future__ import annotations

import math


def padded_vocab(vocab: int, multiple: int = 2048) -> int:
    return -(-vocab // multiple) * multiple


def ramp_sites(n_layers: int, max_sites: int = 12):
    """Layer index after which each ramp sits: ``max_sites`` evenly spaced
    block boundaries, never the last layer."""
    n = min(n_layers - 1, max_sites)
    if n <= 0:
        return ()
    stride = (n_layers - 1) / n
    sites = sorted({int(math.floor((i + 1) * stride)) - 1 for i in range(n)})
    return tuple(s for s in sites if 0 <= s < n_layers - 1) or (0,)


def nest(flat: dict) -> dict:
    """Nested dicts (and one-element lists at integer keys) from flat paths."""
    tree: dict = {}
    for path, v in flat.items():
        node, i = tree, 0
        while i < len(path) - 1:
            if isinstance(path[i + 1], int):
                node = node.setdefault(path[i], [{}])[path[i + 1]]
                i += 2
            else:
                node = node.setdefault(path[i], {})
                i += 1
        node[path[-1]] = v
    return tree


def tree_paths(tree) -> dict:
    """``{path: leaf}`` with plain keys (dict keys and list indices)."""
    import jax

    out = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[tuple(getattr(k, "key", getattr(k, "idx", None)) for k in kp)] = leaf
    return out


def check_layout(layout: dict, abstract) -> None:
    """Raise unless the program's abstract parameter tree is ``layout``
    (``{path: (shape, dtype)}``)."""
    got = {p: (tuple(a.shape), str(a.dtype)) for p, a in tree_paths(abstract).items()}
    want = {p: (tuple(s), t) for p, (s, t) in layout.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()), key=str)
        raise ValueError(f"the program stores its parameters differently from "
                         f"the benchmark's layout: {diff}")


def key_from_seed(seed: int):
    """A JAX key from a seed of any size (seeds may pass 32 bits)."""
    import jax

    from harness.traffic import seed_rng

    return jax.random.PRNGKey(int(seed_rng(seed, 0).integers(0, 2**31 - 1)))
