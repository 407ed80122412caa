"""The sidecar client's packing as the program times it: the
``encode.pack`` span of ``pack_row_matrices`` (the columns assembled and
uploaded, as the client's default ``to_device=True`` packs them); the
median of the program's global sketch ``encode.pack_s{to_device=true}``,
in ms.  Read after the window: the warm-up's requests are in the sketch,
which the median leaves aside."""


def read(ctx):
    from jepsen_tpu.obs.metrics import REGISTRY

    want = (("to_device", "true"),)
    for name, labels, sk in REGISTRY.items():
        if name == "encode.pack_s" and labels == want and sk.count:
            return sk.quantile(0.5) * 1e3
    return None
