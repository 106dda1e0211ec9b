"""What a decode tick of the ``nemotron_h`` cell MUST move, in bytes,
for the two roofline shares its readers report, and device time under a
scope that ``program_trace.KNOWN`` does not list.

The byte counts are lower bounds whatever implements the layer, so a
share of the memory's peak made of them cannot pass 100 %:

- ``state_bytes_a_tick``: every live slot's recurrent state is read and
  written once a Mamba layer (float32, H x P x N), its convolution tail
  is read (K - 1 rows) and the one new row written, and each Mamba
  layer's weights are read once; a dead slot's state need not be
  touched.
- ``latent_moe_bytes_a_tick``: a held expert that at least one live
  token chose is read whole (its TWO matrices, latent x width and back),
  one that none chose need not be; in every expert layer the shared
  expert's two matrices, the router and the two latent projections run
  on every token.
"""

from __future__ import annotations

from benchmark import hbm, program_trace

#: a letter of ``hybrid_override_pattern`` (reference/nemotron_h.py)
MAMBA, EXPERTS = "M", "E"


def state_bytes_a_tick(config: dict, live_slots: float) -> float:
    """Bytes the Mamba layers of the whole model must move in one tick
    that advances ``live_slots`` slots' state."""
    c = config
    size = hbm.DTYPE_BYTES[c["torch_dtype"]]
    h, p, n = c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"]
    d, d_in = c["hidden_size"], h * p
    conv = d_in + 2 * c["n_groups"] * n
    state = 2 * h * p * n * 4                       # float32, in and out
    tail = c["conv_kernel"] * conv * size           # K - 1 rows in, one out
    weights = (
        d * (d_in + conv + h) + d_in * d            # in_proj, out_proj
        + (c["conv_kernel"] + 1) * conv + 3 * h + d_in + d
    ) * size
    layers = c["hybrid_override_pattern"].count(MAMBA)
    return layers * (live_slots * (state + tail) + weights)


def latent_moe_bytes_a_tick(config: dict, experts_hit_a_tick: float) -> float:
    """Bytes the expert layers of the whole model must read in one tick
    in which ``experts_hit_a_tick`` held experts, summed over the expert
    layers, were chosen by some live token."""
    c = config
    size = hbm.DTYPE_BYTES[c["torch_dtype"]]
    d, lat = c["hidden_size"], c["moe_latent_size"]
    expert = 2 * lat * c["moe_intermediate_size"] * size
    every_token = (
        c["n_shared_experts"] * 2 * d
        * c["moe_shared_expert_intermediate_size"]
        + d * c["n_router_outputs"] + 2 * d * lat
    ) * size
    layers = c["hybrid_override_pattern"].count(EXPERTS)
    return experts_hit_a_tick * expert + layers * every_token


def ms_under_a_run(trace: dict | None, scope: str,
                   module: str) -> float | None:
    """``program_trace.ms_under_a_run`` for a scope that its ``KNOWN``
    does not list (``mamba`` and what lies inside it): device
    milliseconds of the operations with ``scope`` as a segment of their
    ``op_name``, inside one run of ``module``, the mean over its runs;
    None where there is no trace, the program did not run or no
    operation carries the scope (the parent commit of the PR that named
    it)."""
    if trace is None:
        return None
    runs = len(program_trace.module_runs(trace, module))
    total = sum(
        dur
        for dev in trace["devices"]
        for _, _, dur, op_name in program_trace.device_ops(dev, module)
        if scope in op_name.split("/")
    )
    if not runs or not total:
        return None
    return total / max(len(trace["devices"]), 1) / 1e6 / runs


def share_of_hbm_peak(run: dict, moved: float, ms: float) -> float:
    """``moved`` bytes in ``ms`` milliseconds as a percentage of what
    the run's chips' memory moves at its peak (``peaks_hbm.json``)."""
    return 100.0 * moved / (
        ms / 1000.0 * run["chips"] * hbm.peak_bytes_per_s(run["device_kind"])
    )
