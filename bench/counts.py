"""Operation and byte counts, worked out from shapes alone.

Model FLOPs (for `step_mfu`) count 2 per multiply-add of the convolutions
and matrix products that the model requires: a SAME convolution's taps
that fall on the zero padding are not counted, nor is elementwise work.
A training sample costs the forward pass, the weight gradients (one
forward's worth) and the input gradients of every layer but the first
(the images need none).

Kernel bytes (for the kernels' achieved bandwidth) count what each call
must move at least once: the wire values, their scales, the server-side
vectors, the mask where one is passed, and the outputs.
"""
from __future__ import annotations

ROWS, LANE = 128, 128
CHUNK = ROWS * LANE  # int8 wire: one f32 scale per CHUNK values


def _same_taps(side: int, k: int) -> int:
    """In-bounds taps of a k-wide SAME window summed over `side` outputs."""
    half = k // 2
    return sum(min(i + half, side - 1) - max(i - half, 0) + 1
               for i in range(side))


def layer_macs(cfg: dict) -> list[int]:
    """Multiply-adds per sample of each layer, input to output."""
    side = cfg["input_shape"][0]
    chans = cfg["input_shape"][2]
    macs = []
    if cfg["model"] == "cnn":
        k = cfg["kernel_size"]
        for cout in (cfg["conv1_channels"], cfg["conv2_channels"]):
            macs.append(_same_taps(side, k) ** 2 * chans * cout)
            side, chans = side // 2, cout
        flat = side * side * chans
        macs += [flat * cfg["fc_dim"], cfg["fc_dim"] * cfg["num_classes"]]
    elif cfg["model"] == "mlr":
        macs.append(side * side * chans * cfg["num_classes"])
    else:
        raise ValueError(f"no FLOP count for model {cfg['model']!r}")
    return macs


def forward_flops(cfg: dict) -> int:
    return 2 * sum(layer_macs(cfg))


def train_flops(cfg: dict) -> int:
    """Forward + weight gradients + input gradients past the first layer."""
    macs = layer_macs(cfg)
    return 2 * (2 * sum(macs) + sum(macs[1:]))


def round_flops(cfg: dict, traffic: dict, eval_round: bool) -> int:
    """Model FLOPs of one federated round of the cell."""
    fl = traffic["fl"]
    samples = fl["clients_per_round"] * fl["local_steps"] * traffic["batch_size"]
    f = samples * train_flops(cfg)
    if eval_round:
        f += traffic["data"]["num_test"] * forward_flops(cfg)
    return f


# ------------------------------------------------------------ kernel bytes


def _groups(n: int, size: int) -> int:
    return max(1, -(-n // size))


def wire_bytes(k: int, n: int, wire: str, group_size: int) -> int:
    """Bytes of a (k, n) client-delta wire: values plus f32 scales."""
    if wire == "f32":
        return 4 * k * n
    if wire == "int8":
        return k * n + 4 * k * _groups(n, CHUNK)
    if wire == "int4":
        return k * -(-n // 2) + 4 * k * _groups(n, group_size)
    raise ValueError(f"no byte count for wire {wire!r}")


def stats_bytes(k: int, n: int, wire: str, group_size: int,
                masked: bool = False) -> int:
    """round_stats: read the wire, g (and the mask); write dots, sqs, sqg."""
    return (wire_bytes(k, n, wire, group_size) + 4 * n * (2 if masked else 1)
            + 4 * (2 * k + 1))


def agg_bytes(k: int, n: int, wire: str, group_size: int) -> int:
    """weighted_agg: read the weights and the wire; write the (n,) sum."""
    return 4 * k + wire_bytes(k, n, wire, group_size) + 4 * n


# A Pallas call shows in the device trace as a `tpu_custom_call`
# instruction named after the jitted wrapper that makes it
# ("%round_stats_q4.7 = ... custom_call_target="tpu_custom_call""):
# wrapper name -> (kind, wire)
KERNELS = {
    "round_stats": ("stats", "f32"),
    "round_stats_q": ("stats", "int8"),
    "round_stats_q4": ("stats", "int4"),
    "weighted_agg": ("agg", "f32"),
    "weighted_agg_q": ("agg", "int8"),
    "weighted_agg_q4": ("agg", "int4"),
}


def kernel_call_bytes(kernel: str, k: int, n: int, group_size: int) -> int:
    kind, wire = KERNELS[kernel]
    if kind == "stats":
        return stats_bytes(k, n, wire, group_size)
    return agg_bytes(k, n, wire, group_size)


def kernel_gbps(trace, kind: str, k: int, n: int, group_size: int):
    """GB/s the kernels of `kind` ("stats" or "agg") achieve in a traced
    window: the bytes their calls need over their summed device time;
    None where none of them ran."""
    need = secs = 0.0
    for kernel, (kernel_kind, _) in KERNELS.items():
        if kernel_kind != kind:
            continue
        events = trace.kernel_events(kernel)
        secs += sum(e.end - e.start for e in events)
        need += len(events) * kernel_call_bytes(kernel, k, n, group_size)
    return need / secs / 1e9 if secs else None
