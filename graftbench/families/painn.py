"""PaiNN (``model_type: "PAINN"``): the plain encoder and its counts.

Schütt, Unke, Gastegger, "Equivariant message passing for the prediction of
tensorial properties and molecular spectra", ICML 2021, arXiv:2102.03150
(``schnetpack.representation.PaiNN``). With j the sender, i the receiver,
``r_ij = r_j - r_i``, ``d = |r_ij|``, ``u = r_ij / d``, SiLU throughout,
F = ``hidden_dim``, ``r_c`` = ``radius``:

  s0 = Dense(x), v0 = 0 in R^{3 x F}
  phi_n(d) = sin(n pi d / r_c) / d, n = 1..num_radial
  f_c(d)   = (cos(pi d / r_c) + 1) / 2 for d < r_c, else 0
  W_ij     = Dense_{num_radial -> 3F}(phi(d)) f_c(d)              a block
  message  x = Dense_{F->3F}(SiLU(Dense_{F->F}(s))) at the nodes;
           (a, b, c) = split(x_j * W_ij);  s_i += sum_j a_ij;
           v_i += sum_j (b_ij * v_j + c_ij (x) u_ij)
  update   (Uv, Vv) = split(Dense_{F->2F, no bias}(v)) on the channel axis;
           n = sqrt(sum_xyz Vv^2 + 1e-8);
           (a_vv, a_sv, a_ss) = split(Dense_{F->3F}(SiLU(Dense_{2F->F}([s, n]))));
           v += a_vv * Uv;  s += a_sv * sum_xyz(Uv * Vv) + a_ss

Here ``v`` is ``[n, 3, F]`` and every sum is ``x.at[recv].add``; the program
keeps ``v`` flat ``[N, 3F]`` and sums over a sorted edge list.

Departures from the paper, each as the program has them:
  * Energy only: the paper trains MD17 on energies and forces; forces
    (-dE/dpositions) are ROADMAP R6.
  * The read-out is this system's (``reference.py``): mean pool over the
    atoms' ``s``, the shared MLP, the head's MLP; the paper's is atomwise
    ``F -> F/2 -> 1`` summed over atoms.
  * ``s0`` is a Dense of the node features (the min-max-scaled atomic
    number), not an embedding table of Z: this system's node features are
    floats.
  * No batch norm, no ReLU and no dropout in the encoder, as in the paper
    (the other families of this system have all three).
"""

import jax
import jax.numpy as jnp

from graftbench import flops, reference

# Program against reference, |a - b| <= ATOL + RTOL |b|, this family's own.
# The configuration states float32 and the program runs every Dense of the
# family at ``Precision.HIGHEST``. PaiNN has no norm layer, so what decides is
# set between two readings on the chip at F 128 / 3 blocks, the harness's 8
# graphs, outputs of O(0.3) (my chip runs, PR 26): the LARGEST the program as
# handed in reads over 15 seeds is 6.9e-7 (smallest 2.1e-7); the SMALLEST that
# the precision below float32 reads is 1.1e-3 (one bf16 pass in the shared
# and head MLPs alone, eight seeds: 1.1e-3 to 3.4e-3; the TPU's default single
# bf16 pass everywhere: 3.0e-3 and 3.6e-3). ``reference.py``'s 5e-3 passes all
# of those, so a change that dropped ``HIGHEST`` would still read correct;
# 1e-4 is 145 times the first reading and a tenth of the second.
ATOL = RTOL = 1e-4


def encode(model, params, stats, graph):
    pos, send, recv = graph["pos"], graph["send"], graph["recv"]
    n, f, r_c = graph["x"].shape[0], model.hidden_dim, model.radius
    r = pos[send] - pos[recv]
    d = jnp.linalg.norm(r, axis=-1, keepdims=True)  # [E, 1]
    u = r / d
    k = jnp.arange(1, model.num_radial + 1, dtype=jnp.float32)
    phi = jnp.sin(k * jnp.pi * d / r_c) / d  # [E, num_radial]
    f_c = jnp.where(d < r_c, 0.5 * (jnp.cos(jnp.pi * d / r_c) + 1.0), 0.0)

    s = reference.dense(params["conv_embed"], graph["x"])
    v = jnp.zeros((n, 3, f), jnp.float32)
    for li in range(model.num_conv_layers):
        p = params[f"conv_{li}"]
        x = reference.dense(p["msg_1"], jax.nn.silu(reference.dense(p["msg_0"], s)))
        w = reference.dense(p["filter"], phi) * f_c
        a, b, c = jnp.split(x[send] * w, 3, axis=-1)
        s = s + jnp.zeros((n, f), jnp.float32).at[recv].add(a)
        v = v + jnp.zeros((n, 3, f), jnp.float32).at[recv].add(
            b[:, None, :] * v[send] + c[:, None, :] * u[:, :, None]
        )
        uv, vv = jnp.split(v @ p["vec"]["kernel"], 2, axis=-1)  # [n, 3, F] each
        norm = jnp.sqrt((vv * vv).sum(axis=1) + 1e-8)
        g = reference.dense(
            p["upd_1"],
            jax.nn.silu(reference.dense(p["upd_0"], jnp.concatenate([s, norm], axis=-1))),
        )
        a_vv, a_sv, a_ss = jnp.split(g, 3, axis=-1)
        v = v + a_vv[:, None, :] * uv
        s = s + a_sv * (uv * vv).sum(axis=1) + a_ss
    return s


def _geometry(edges: int, radial: int) -> dict:
    """Once a step, from the two gathered ``[E, 3]`` position rows: ``r_ij``,
    ``d``, ``u`` (3 + 5 + 3 operations an edge), the basis (a multiply, a sine
    and a division a function) and the cutoff (4). Reads the two ``[E, 3]``
    rows, writes ``u`` ``[E, 3]``, the cutoff ``[E]`` and the basis ``[E,
    radial]``. No backward: nothing is differentiated with respect to
    positions (forces are ROADMAP R6)."""
    return flops.part(
        edges * (15 + 3 * radial), flops.B * edges * (6 + 3 + 1 + radial), 0
    )


def _filter(edges: int, radial: int, f: int) -> dict:
    """A block's filter ``Dense(phi) f_c``: reads the basis, the weights and
    the cutoff, writes ``[E, 3F]``. Its backward is the weights' gradient
    alone (the basis needs none): the ``[E, 3F]`` cotangent and the basis
    read, the weights written -- one forward's bytes, not ``flops.part``'s
    two."""
    fwd = flops.B * (edges * radial + radial * 3 * f + 3 * f + edges + edges * 3 * f)
    return flops.part(2 * edges * radial * 3 * f + 2 * edges * 3 * f, fwd, fwd)


def block_counts(nodes: int, edges: int, f: int, radial: int, first: bool = False) -> list:
    """One message and one update block, forward. In the ``first`` block
    ``v`` is zero, so no gradient flows back through ``v_j`` and the compiler
    drops that scatter-add (the forward gather of zeros runs)."""
    w = 3 * f
    return [
        # message: the two node Denses, the two 3F-wide gathers (x, v), the
        # filter, the products, the ONE 4F-wide sum of [a | b v_j + c (x) u]
        flops.dense(nodes, f, f),
        flops.part(nodes * f * 4, flops.B * 2 * nodes * f),  # SiLU
        flops.dense(nodes, f, w),
        flops.gather(nodes, edges, w),  # x_j
        flops.gather(nodes, edges, w, grad=not first),  # v_j
        _filter(edges, radial, f),
        # x_j * W: reads two [E, 3F], writes one; then b * v_j + c (x) u:
        # reads b, c [E, F], v_j [E, 3F], u [E, 3], writes [E, 3F].
        flops.part(
            edges * w + 3 * edges * w,
            flops.B * (3 * edges * w + 2 * edges * f + 2 * edges * w + 3 * edges),
        ),
        flops.segment_reduce(edges, nodes, f + w, ops=edges * (f + w)),
        flops.part(nodes * (f + w), flops.B * 3 * nodes * (f + w)),  # s +=, v +=
        # update: the channel mix of v (no bias), the norm, the two Denses,
        # the gated residuals
        flops.part(
            2 * 3 * nodes * f * 2 * f,
            flops.B * (3 * nodes * f + f * 2 * f + 3 * nodes * 2 * f),
        ),
        flops.part(nodes * f * 8, flops.B * (3 * nodes * f + nodes * f)),  # norm
        flops.dense(nodes, 2 * f, f),
        flops.part(nodes * f * 4, flops.B * 2 * nodes * f),  # SiLU
        flops.dense(nodes, f, w),
        # v += a_vv * Uv; s += a_sv * sum_xyz(Uv * Vv) + a_ss
        flops.part(
            nodes * (2 * w + 6 * f + 2 * f),
            flops.B * (nodes * (f + 2 * w + w) + nodes * (2 * f + 2 * w + 2 * f)),
        ),
    ]


def counts(arch: dict, nodes: int, edges: int):
    f, radial = arch["hidden_dim"], arch["num_radial"]
    parts = [
        flops.dense(nodes, arch["input_dim"], f),  # s0
        flops.gather(nodes, edges, 3, grad=False),  # r_j
        flops.gather(nodes, edges, 3, grad=False),  # r_i
        _geometry(edges, radial),
    ]
    for li in range(arch["num_conv_layers"]):
        parts += block_counts(nodes, edges, f, radial, first=li == 0)
    return parts, f


def geom_bytes(arch: dict, nodes: int, edges: int) -> dict:
    """``{"fwd", "bwd"}`` bytes of what runs under ``hydragnn.geom`` (the
    edge geometry once a step and a filter a block), by ``flops.py``'s
    convention; the two position gathers inside it are ``gather``'s."""
    f, radial = arch["hidden_dim"], arch["num_radial"]
    parts = [_geometry(edges, radial)] + [
        _filter(edges, radial, f) for _ in range(arch["num_conv_layers"])
    ]
    return {d: sum(p[d] for p in parts) for d in ("fwd", "bwd")}
