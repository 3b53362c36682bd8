"""Pure-JAX 3D articulated-body physics — the spatial generalization of
:mod:`d4pg_tpu.envs.planar`.

Why this exists: Humanoid is the reference's scale-out task (env capability
``main.py:42,68``, worker fan-out ``main.py:399-403``) and the one
BASELINE.json config whose host path ships the widest rows host→device
(348-dim observations, twice per transition). The
planar engine's own docstring argues its design generalizes to 3D; this
module is that generalization, so Humanoid rolls out ON the TPU inside the
same XLA program as the learner.

Same design rules as the planar engine, extended to SO(3):

- **Hand-written forward kinematics only.** Bodies carry world origin
  ``o ∈ R³`` and rotation ``R ∈ SO(3)``; free joints set the frame from
  qpos directly (MuJoCo semantics), hinges rotate about a body-frame axis
  anchored at a body-frame point (Rodrigues), slides translate.
- **Quasi-velocities, not quaternion rates.** The velocity state v ∈ R^nv
  follows MuJoCo's convention exactly (verified empirically against
  ``mj_fullM``): free joints carry world-frame linear velocity + BODY-frame
  angular velocity; the tangent lift q̇ = L(q)v maps ω into quaternion
  rates via q̇_quat = ½ u ⊗ (0, ω). All autodiff happens through this lift.
- **Mass matrix is still one ``jax.hessian``.** Kinetic energy
  ``T(q, v) = ½Σ m|ċom|² + ½Σ ω·I_b·ω + ½Σ armature·v²`` is computed by a
  ``jax.jvp`` through FK and is exactly quadratic in v, so
  ``M(q) = ∂²T/∂v²`` — matches ``mj_fullM`` (tests/test_spatial.py).
- **Bias force by Newton–Euler through autodiff** (Jourdain's principle),
  not Boltzmann–Hamel bookkeeping: a second ``jvp`` along the flow at
  v̇ = 0 yields the coriolis accelerations (a_com, ω̇); per-body wrenches
  ``f = m(a_com + g ẑ)`` and ``τ = I ω̇ + ω × I ω`` pull back to
  generalized coordinates through the transpose of the velocity map
  (one ``jax.vjp``). Matches ``mj_rne(flg_acc=0)``.
- **Contacts: penalty spheres vs the ground plane**, as in planar — but
  note the gym humanoid's feet ARE spheres, so ground contact during
  locomotion is geometrically exact; capsule endpoints approximate the
  rest (falls). Friction is isotropic regularized Coulomb in the tangent
  plane. Self-collision is not modeled (documented deviation, as is the
  penalty-vs-soft-LCP trade; see planar.py docstring).

Integration is semi-implicit Euler under ``lax.scan`` with exact
quaternion exponential updates (renormalized each substep).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# joint type codes (ours, not MuJoCo's)
FREE, HINGE, SLIDE = 0, 1, 2


class SpatialModel(NamedTuple):
    """Static description of a 3D kinematic tree. Structure fields are
    host-side numpy (consumed at trace time); numeric fields become jnp
    constants inside jit."""

    # tree structure (movable bodies only; index 0 = first child of world)
    parent: np.ndarray        # [NB] int, -1 = world
    body_pos: np.ndarray      # [NB, 3] frame offset in parent frame
    body_quat: np.ndarray     # [NB, 4] frame rotation in parent frame (wxyz)
    # joints, in MuJoCo joint order
    jnt_body: np.ndarray      # [NJ] int body index
    jnt_type: np.ndarray      # [NJ] FREE | HINGE | SLIDE
    jnt_axis: np.ndarray      # [NJ, 3] hinge/slide axis in body frame (unit)
    jnt_pos: np.ndarray       # [NJ, 3] hinge anchor in body frame
    jnt_qposadr: np.ndarray   # [NJ] int index into qpos
    jnt_dofadr: np.ndarray    # [NJ] int index into qvel
    qpos0: np.ndarray         # [NQ] joint reference (XML pose)
    nq: int
    nv: int
    # per-body mass properties
    mass: np.ndarray          # [NB]
    ipos: np.ndarray          # [NB, 3] COM in body frame
    inertia: np.ndarray       # [NB, 3, 3] full inertia tensor about the COM,
                              # in the BODY frame (R_iquat diag(I) R_iquatᵀ)
    # per-dof / per-joint passive+actuation parameters
    armature: np.ndarray      # [NV]
    damping: np.ndarray       # [NV]
    stiffness: np.ndarray     # [NJ] spring toward qpos_spring (scalar joints)
    spring_ref: np.ndarray    # [NJ]
    limited: np.ndarray       # [NJ] bool (scalar joints only)
    range_lo: np.ndarray      # [NJ]
    range_hi: np.ndarray      # [NJ]
    gear: np.ndarray          # [NU] actuator gear
    act_dof: np.ndarray       # [NU] int dof driven by each actuator
    ctrl_hi: np.ndarray       # [NU] ctrlrange upper bound (actions in (−1,1)
                              # are scaled by this; gym humanoid = 0.4)
    # contact spheres (capsule endpoints + sphere geoms)
    con_body: np.ndarray      # [NC] int body index
    con_pos: np.ndarray       # [NC, 3] point in body frame
    con_radius: np.ndarray    # [NC]
    friction: np.ndarray      # [NC] sliding friction coefficient
    # world / integration
    gravity: float
    timestep: float
    # contact penalty parameters — same calibrated family as planar.py
    contact_stiffness: float
    contact_damping: float
    slip_vel: float
    limit_stiffness: float
    limit_damping: float


def extract_spatial_model(
    xml_path: str,
    contact_stiffness: float = 60_000.0,
    contact_damping: float = 350.0,
    slip_vel: float = 0.05,
    limit_stiffness: float = 400.0,
    limit_damping: float = 4.0,
) -> SpatialModel:
    """Build a :class:`SpatialModel` from any free/hinge/slide MJCF via the
    host MuJoCo compiler (model DATA only — the dynamics are ours)."""
    import mujoco

    m = mujoco.MjModel.from_xml_path(xml_path)
    nb = m.nbody - 1  # drop world

    def b2i(mj_body: int) -> int:
        return mj_body - 1

    parent = np.array([b2i(m.body_parentid[b + 1]) for b in range(nb)])
    body_pos = np.array([m.body_pos[b + 1] for b in range(nb)])
    body_quat = np.array([m.body_quat[b + 1] for b in range(nb)])
    mass = np.array([m.body_mass[b + 1] for b in range(nb)])
    ipos = np.array([m.body_ipos[b + 1] for b in range(nb)])
    inertia = np.empty((nb, 3, 3))
    for b in range(nb):
        R = np.zeros(9)
        mujoco.mju_quat2Mat(R, m.body_iquat[b + 1])
        R = R.reshape(3, 3)
        inertia[b] = R @ np.diag(m.body_inertia[b + 1]) @ R.T

    nj = m.njnt
    jnt_body = np.array([b2i(m.jnt_bodyid[j]) for j in range(nj)])
    jnt_type = np.empty(nj, np.int64)
    for j in range(nj):
        t = m.jnt_type[j]
        if t == mujoco.mjtJoint.mjJNT_FREE:
            jnt_type[j] = FREE
        elif t == mujoco.mjtJoint.mjJNT_HINGE:
            jnt_type[j] = HINGE
        elif t == mujoco.mjtJoint.mjJNT_SLIDE:
            jnt_type[j] = SLIDE
        else:
            raise ValueError(f"joint {j}: ball joints not supported yet")

    con_body, con_pos, con_radius, friction = [], [], [], []
    for g in range(m.ngeom):
        b = m.geom_bodyid[g]
        if b == 0:
            continue
        gtype = m.geom_type[g]
        gpos = np.array(m.geom_pos[g])
        if gtype == mujoco.mjtGeom.mjGEOM_CAPSULE:
            R = np.zeros(9)
            mujoco.mju_quat2Mat(R, m.geom_quat[g])
            axis = R.reshape(3, 3)[:, 2]  # capsule local axis is z
            half = m.geom_size[g][1]
            ends = [gpos - half * axis, gpos + half * axis]
        elif gtype == mujoco.mjtGeom.mjGEOM_SPHERE:
            ends = [gpos]
        else:
            raise ValueError(f"geom {g}: only capsule/sphere collide in spatial")
        for e in ends:
            con_body.append(b2i(b))
            con_pos.append(e)
            con_radius.append(m.geom_size[g][0])
            friction.append(m.geom_friction[g][0])

    nu = m.nu
    act_jnt = [m.actuator_trnid[u][0] for u in range(nu)]

    return SpatialModel(
        parent=parent,
        body_pos=body_pos,
        body_quat=body_quat,
        jnt_body=jnt_body,
        jnt_type=jnt_type,
        jnt_axis=np.array(m.jnt_axis),
        jnt_pos=np.array(m.jnt_pos),
        jnt_qposadr=np.array(m.jnt_qposadr),
        jnt_dofadr=np.array(m.jnt_dofadr),
        qpos0=np.array(m.qpos0),
        nq=int(m.nq),
        nv=int(m.nv),
        mass=mass,
        ipos=ipos,
        inertia=inertia,
        armature=np.array(m.dof_armature),
        damping=np.array(m.dof_damping),
        stiffness=np.array(m.jnt_stiffness),
        spring_ref=np.array(
            [m.qpos_spring[m.jnt_qposadr[j]] for j in range(nj)]
        ),
        limited=np.array([bool(m.jnt_limited[j]) for j in range(nj)]),
        range_lo=np.array(m.jnt_range[:, 0]),
        range_hi=np.array(m.jnt_range[:, 1]),
        gear=np.array([m.actuator_gear[u][0] for u in range(nu)]),
        act_dof=np.array([m.jnt_dofadr[j] for j in act_jnt]),
        ctrl_hi=np.array(
            [
                m.actuator_ctrlrange[u][1]
                if m.actuator_ctrllimited[u]
                else 1.0
                for u in range(nu)
            ]
        ),
        con_body=np.array(con_body),
        con_pos=np.array(con_pos),
        con_radius=np.array(con_radius),
        friction=np.array(friction),
        gravity=float(-m.opt.gravity[2]),
        timestep=float(m.opt.timestep),
        contact_stiffness=contact_stiffness,
        contact_damping=contact_damping,
        slip_vel=slip_vel,
        limit_stiffness=limit_stiffness,
        limit_damping=limit_damping,
    )


# ---------------------------------------------------------------------------
# SO(3) helpers (wxyz quaternions, matching MuJoCo)
# ---------------------------------------------------------------------------


def quat_mul(a: jax.Array, b: jax.Array) -> jax.Array:
    w1, v1 = a[0], a[1:]
    w2, v2 = b[0], b[1:]
    return jnp.concatenate(
        [(w1 * w2 - v1 @ v2)[None], w1 * v2 + w2 * v1 + jnp.cross(v1, v2)]
    )


def quat_to_mat(u: jax.Array) -> jax.Array:
    w, x, y, z = u[0], u[1], u[2], u[3]
    return jnp.array(
        [
            [1 - 2 * (y * y + z * z), 2 * (x * y - w * z), 2 * (x * z + w * y)],
            [2 * (x * y + w * z), 1 - 2 * (x * x + z * z), 2 * (y * z - w * x)],
            [2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x * x + y * y)],
        ]
    )


def _axis_angle_mat(axis: jax.Array, theta: jax.Array) -> jax.Array:
    """Rodrigues: rotation by theta about a (static, unit) body-frame axis."""
    K = jnp.array(
        [
            [0.0, -axis[2], axis[1]],
            [axis[2], 0.0, -axis[0]],
            [-axis[1], axis[0], 0.0],
        ]
    )
    return jnp.eye(3) + jnp.sin(theta) * K + (1.0 - jnp.cos(theta)) * (K @ K)


def _quat_exp(phi: jax.Array) -> jax.Array:
    """exp map: rotation vector φ → unit quaternion (safe at ‖φ‖ → 0)."""
    half = 0.5 * jnp.sqrt(jnp.sum(phi**2) + 1e-30)
    # sin(half)/half via sinc keeps the φ→0 limit exact and differentiable
    return jnp.concatenate(
        [jnp.cos(half)[None], 0.5 * phi * jnp.sinc(half / jnp.pi)]
    )


# ---------------------------------------------------------------------------
# Kinematics
# ---------------------------------------------------------------------------


def lift_velocity(model: SpatialModel, q: jax.Array, v: jax.Array) -> jax.Array:
    """Tangent lift q̇ = L(q) v — maps quasi-velocities (MuJoCo qvel
    conventions) to qpos rates. Free joints: q̇_pos = v_lin (world),
    q̇_quat = ½ u ⊗ (0, ω_body)."""
    dq = jnp.zeros(model.nq, q.dtype)
    for j in range(len(model.jnt_body)):
        qa, da = int(model.jnt_qposadr[j]), int(model.jnt_dofadr[j])
        if int(model.jnt_type[j]) == FREE:
            dq = dq.at[qa : qa + 3].set(v[da : da + 3])
            u = q[qa + 3 : qa + 7]
            omega = v[da + 3 : da + 6]
            dq = dq.at[qa + 3 : qa + 7].set(
                0.5 * quat_mul(u, jnp.concatenate([jnp.zeros(1), omega]))
            )
        else:
            dq = dq.at[qa].set(v[da])
    return dq


def fk(model: SpatialModel, q: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Forward kinematics: world (origin [NB,3], rotation [NB,3,3]) per
    body. Unrolled over the static tree at trace time; joints compose in
    MuJoCo order within each body."""
    nb = len(model.parent)
    joints_of = [[] for _ in range(nb)]
    for j in range(len(model.jnt_body)):
        joints_of[int(model.jnt_body[j])].append(j)
    origins: list = [None] * nb
    rots: list = [None] * nb
    for b in range(nb):
        p = int(model.parent[b])
        if p < 0:
            o, R = jnp.zeros(3), jnp.eye(3)
        else:
            o, R = origins[p], rots[p]
        o = o + R @ jnp.asarray(model.body_pos[b])
        R = R @ quat_to_mat(jnp.asarray(model.body_quat[b]))
        for j in joints_of[b]:
            qa = int(model.jnt_qposadr[j])
            t = int(model.jnt_type[j])
            if t == FREE:
                # free joint = the body frame itself, in world coordinates
                o = q[qa : qa + 3]
                R = quat_to_mat(q[qa + 3 : qa + 7])
            elif t == SLIDE:
                dq = q[qa] - model.qpos0[qa]
                o = o + R @ jnp.asarray(model.jnt_axis[j]) * dq
            else:  # hinge about a body-frame axis anchored at jnt_pos
                dq = q[qa] - model.qpos0[qa]
                anchor = o + R @ jnp.asarray(model.jnt_pos[j])
                R = R @ _axis_angle_mat(jnp.asarray(model.jnt_axis[j]), dq)
                o = anchor - R @ jnp.asarray(model.jnt_pos[j])
        origins[b] = o
        rots[b] = R
    return jnp.stack(origins), jnp.stack(rots)


def body_coms(model: SpatialModel, q: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """World COM positions [NB,3] and rotations [NB,3,3]."""
    origins, rots = fk(model, q)
    coms = origins + jnp.einsum("bij,bj->bi", rots, jnp.asarray(model.ipos))
    return coms, rots


def com_velocities(
    model: SpatialModel, q: jax.Array, v: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """(ċom [NB,3] world, ω [NB,3] BODY frame) — linear in v. The body-frame
    angular velocity comes from Ṙ = R[ω]× ⇒ [ω]× = RᵀṘ."""
    dq = lift_velocity(model, q, v)
    (coms, rots), (dcoms, drots) = jax.jvp(
        lambda qq: body_coms(model, qq), (q,), (dq,)
    )
    W = jnp.einsum("bji,bjk->bik", rots, drots)  # RᵀṘ, antisymmetric
    omega = 0.5 * jnp.stack(
        [
            W[:, 2, 1] - W[:, 1, 2],
            W[:, 0, 2] - W[:, 2, 0],
            W[:, 1, 0] - W[:, 0, 1],
        ],
        axis=-1,
    )
    return dcoms, omega


# ---------------------------------------------------------------------------
# Dynamics
# ---------------------------------------------------------------------------


def kinetic_energy(model: SpatialModel, q: jax.Array, v: jax.Array) -> jax.Array:
    """T(q, v) incl. rotor armature — quadratic in v by construction."""
    dcoms, omega = com_velocities(model, q, v)
    T = 0.5 * jnp.sum(jnp.asarray(model.mass) * jnp.sum(dcoms**2, axis=-1))
    T = T + 0.5 * jnp.einsum(
        "bi,bij,bj->", omega, jnp.asarray(model.inertia), omega
    )
    T = T + 0.5 * jnp.sum(jnp.asarray(model.armature) * v**2)
    return T


def mass_matrix(model: SpatialModel, q: jax.Array) -> jax.Array:
    """M(q) = ∂²T/∂v² — exact (T is quadratic in v), matches mj_fullM."""
    return jax.hessian(lambda vv: kinetic_energy(model, q, vv))(
        jnp.zeros(model.nv, q.dtype)
    )


def bias_force(model: SpatialModel, q: jax.Array, v: jax.Array) -> jax.Array:
    """c(q, v) with M(q)v̇ + c(q, v) = τ_applied. Newton–Euler through
    autodiff: differentiate the velocity map along the flow at v̇ = 0 to get
    coriolis accelerations, form per-body wrenches, pull back through the
    transpose of the (linear-in-v) velocity map. Matches mj_rne(flg_acc=0)
    (coriolis + centrifugal + gyroscopic + gravity)."""
    dq = lift_velocity(model, q, v)
    (dcoms, omega), (acoms, domega) = jax.jvp(
        lambda qq: com_velocities(model, qq, v), (q,), (dq,)
    )
    inertia = jnp.asarray(model.inertia)
    f_com = jnp.asarray(model.mass)[:, None] * (
        acoms + jnp.array([0.0, 0.0, model.gravity])
    )
    Iw = jnp.einsum("bij,bj->bi", inertia, omega)
    tau_body = jnp.einsum("bij,bj->bi", inertia, domega) + jnp.cross(omega, Iw)
    _, vjp_fn = jax.vjp(lambda vv: com_velocities(model, q, vv), v)
    return vjp_fn((f_com, tau_body))[0]


def contact_points(model: SpatialModel, q: jax.Array) -> jax.Array:
    """World positions [NC, 3] of all contact spheres."""
    origins, rots = fk(model, q)
    o = origins[jnp.asarray(model.con_body)]
    R = rots[jnp.asarray(model.con_body)]
    return o + jnp.einsum("cij,cj->ci", R, jnp.asarray(model.con_pos))


def _applied_force(
    model: SpatialModel, q: jax.Array, v: jax.Array, ctrl: jax.Array
) -> jax.Array:
    """All generalized forces except bias: actuation, passive spring/damper,
    joint-limit penalty, ground contact. ``ctrl`` is in actuator units
    (callers scale canonical (−1,1) actions by ctrl_hi)."""
    f = jnp.zeros(model.nv, q.dtype).at[jnp.asarray(model.act_dof)].add(
        jnp.asarray(model.gear) * ctrl
    )
    f = f - jnp.asarray(model.damping) * v

    # joint springs + limits act on scalar joints only (free dofs have none)
    scalar = [
        j for j in range(len(model.jnt_body)) if int(model.jnt_type[j]) != FREE
    ]
    if scalar:
        qadr = np.array([model.jnt_qposadr[j] for j in scalar])
        dadr = np.array([model.jnt_dofadr[j] for j in scalar])
        qj = q[qadr]
        stiff = jnp.asarray(model.stiffness[scalar])
        ref = jnp.asarray(model.spring_ref[scalar])
        fj = -stiff * (qj - ref)
        lim = jnp.asarray(model.limited[scalar], q.dtype)
        lo = jnp.asarray(model.range_lo[scalar])
        hi = jnp.asarray(model.range_hi[scalar])
        over = jnp.maximum(qj - hi, 0.0)
        under = jnp.maximum(lo - qj, 0.0)
        fj = fj - lim * model.limit_stiffness * (over - under)
        fj = fj - lim * model.limit_damping * v[dadr] * ((over > 0) | (under > 0))
        f = f.at[dadr].add(fj)

    # Ground contact: penalty normal + regularized isotropic Coulomb
    # friction in the tangent plane. Unlike the planar engine, q-space (nq,
    # with quaternions) ≠ v-space (nv), so the contact Jacobian transpose
    # must include the tangent lift: ṗ = (∂p/∂q) L(q) v ⇒ τ = Lᵀ (∂p/∂q)ᵀ f.
    # Both directions come from autodiff of the same map pvel: v ↦ ṗ.
    points = contact_points(model, q)

    def pvel(vv):
        return jax.jvp(
            lambda qq: contact_points(model, qq),
            (q,),
            (lift_velocity(model, q, vv),),
        )[1]

    vels, vjp_fn = jax.vjp(pvel, v)
    phi = points[:, 2] - jnp.asarray(model.con_radius)  # signed gap to z=0
    pen = jnp.maximum(-phi, 0.0)
    active = pen > 0.0
    fn = model.contact_stiffness * pen - model.contact_damping * vels[:, 2] * active
    fn = jnp.maximum(fn, 0.0)
    vt = vels[:, :2]
    speed = jnp.sqrt(jnp.sum(vt**2, axis=-1) + 1e-12)
    ft = (
        -jnp.asarray(model.friction)[:, None]
        * fn[:, None]
        * jnp.tanh(speed / model.slip_vel)[:, None]
        * vt
        / speed[:, None]
    )
    f_points = jnp.concatenate([ft, fn[:, None]], axis=-1)
    return f + vjp_fn(f_points)[0]


def forward_dynamics(
    model: SpatialModel, q: jax.Array, v: jax.Array, ctrl: jax.Array
) -> jax.Array:
    """v̇ = M(q)⁻¹ (f_applied − c(q, v)). nv×nv solve (23×23 for humanoid)."""
    M = mass_matrix(model, q)
    rhs = _applied_force(model, q, v, ctrl) - bias_force(model, q, v)
    return jnp.linalg.solve(M, rhs)


def integrate_qpos(
    model: SpatialModel, q: jax.Array, v: jax.Array, dt: float
) -> jax.Array:
    """q ← q ⊕ dt·v: linear dofs integrate additively; free-joint
    quaternions by the exact exponential map (renormalized)."""
    q2 = q + dt * lift_velocity(model, q, v)
    for j in range(len(model.jnt_body)):
        if int(model.jnt_type[j]) != FREE:
            continue
        qa, da = int(model.jnt_qposadr[j]), int(model.jnt_dofadr[j])
        u = q[qa + 3 : qa + 7]
        u2 = quat_mul(u, _quat_exp(dt * v[da + 3 : da + 6]))
        q2 = q2.at[qa + 3 : qa + 7].set(u2 / jnp.linalg.norm(u2))
    return q2


def step_physics(
    model: SpatialModel,
    q: jax.Array,
    v: jax.Array,
    ctrl: jax.Array,
    n_substeps: int,
    substep_dt: float,
) -> Tuple[jax.Array, jax.Array]:
    """Semi-implicit Euler over a lax.scan of substeps (control held)."""

    def sub(carry, _):
        q, v = carry
        vdot = forward_dynamics(model, q, v, ctrl)
        v = v + substep_dt * vdot
        q = integrate_qpos(model, q, v, substep_dt)
        return (q, v), None

    (q, v), _ = jax.lax.scan(sub, (q, v), None, length=n_substeps)
    return q, v
