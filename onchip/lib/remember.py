"""A loader for a check that builds on another check: the harness's by-name
loader, but the reference remembers what it was last asked for and the rows
generator the rows it made, so that the rows are made once a comparison and no
state is streamed twice."""

import types


def remembering(load):
    """-> (load', kept): `load'` is `load` with `references/*` and `rows/*`
    wrapped; `kept` fills with "rows" (the generator's last), "z", "pe" and
    "grad" (the reference's last call)."""
    kept = {}

    def loader(folder, name):
        mod = load(folder, name)

        def potential_and_grad(rows, z):
            kept["z"] = z
            kept["pe"], kept["grad"] = mod.potential_and_grad(rows, z)
            return kept["pe"], kept["grad"]

        def make(*args):
            kept["rows"] = mod.make(*args)
            return kept["rows"]

        more = {"references": {"potential_and_grad": potential_and_grad},
                "rows": {"make": make}}.get(folder)
        return mod if more is None else types.SimpleNamespace(
            **dict(vars(mod), **more))

    return loader, kept
