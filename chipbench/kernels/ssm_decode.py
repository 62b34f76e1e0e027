"""The per-token state update and read-out of a state-space layer
(``ops/pallas_kernels/ssm_decode.py``): for every live sequence and
head, ``S <- decay * S + xdt (x) B`` and ``y = S C`` on a float32
``[P, N]`` state.

PATTERNS matches the Pallas kernel's event, named after the jitted
wrapper around its ``pallas_call``.

The count is per layer and per decode call, for the LIVE sequences (the
kernel also copies a slot that is not live through, which is no work the
algorithm needs): the state read and written once, plus the small
operands and the output; per state element two multiplies and an add to
update it and a multiply-add to read it out.
"""

PATTERNS = {
    "decode": [r"^_ssm_decode_call\S* \[tpu_custom_call\]"],
}


def shape(cfg, batch):
    return {"B": batch, "heads": cfg["mamba_n_heads"],
            "P": cfg["mamba_d_head"], "N": cfg["mamba_d_state"],
            "itemsize": 4}


def flops(sh, phase="decode"):
    return 5 * sh["B"] * sh["heads"] * sh["P"] * sh["N"]


def bytes(sh, phase="decode"):
    state = 2 * sh["B"] * sh["heads"] * sh["P"] * sh["N"] * sh["itemsize"]
    # decay and xdt in, y out, as [heads * P] rows; B and C [N]
    small = sh["B"] * (3 * sh["heads"] * sh["P"] + 2 * sh["N"]) \
        * sh["itemsize"]
    return state + small
