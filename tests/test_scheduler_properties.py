"""Property-based serving-invariant harness for ``Scheduler.form_batch``.

A model-based simulation drives arbitrary request streams — mixed
policies (including compatible static-schedule families), deadlines,
arrival gaps, interleaved cut attempts, and a final drain — through the
scheduler in both formation modes, then checks the serving invariants
on the full cut history:

* **conservation** — no request is dropped or duplicated across cuts;
* **stable FIFO within a compatibility group** — a request served
  while not deadline-lapsed is never overtaken by a later submission of
  its own group (ungrouped: of the whole queue), and every batch lists
  its requests in submission order;
* **deadline promotion** — whenever a batch is cut while lapsed
  requests exist, the cut is taken from the group of the most-overdue
  one and contains its lapsed members up to ``max_batch`` (ungrouped:
  the FIFO-first lapsed requests), so a lapsed request is served by the
  very next cut of its group and can never be starved;
* **policy purity** — under ``group_policies=True`` every emitted
  batch is policy-homogeneous (one compatibility key), and the plan's
  ``group_key`` matches its members;
* **shape purity** — in EVERY mode (mixed shapes cannot share one
  executable) each cut resolves to a single (latent, CRF) shape key,
  the plan carries it, and deadline promotion never leaks across
  shapes: the promoted set is the lapsed members of the cut's own
  (shape, group), so a lapsed 512-token request can never be pulled
  into a 256-token batch;
* **bucketing** — ``bucket`` is a ladder signature that fits
  ``n_real`` (exactly ``bucket_for`` unless ``pad_to_max``).

The same checker runs under Hypothesis (the CI property job:
``--hypothesis-profile=ci --hypothesis-seed=0``) and on deterministic
regression streams that exercise each invariant without hypothesis
installed (the bare tier-1 environment).
"""
import dataclasses

import pytest

from repro.core import policies
from repro.serving.scheduler import (DiffusionRequest, Scheduler,
                                     bucket_for, bucket_sizes)

# property tests skip gracefully when hypothesis is absent (CI installs
# it via `pip install -e .[dev]`); the deterministic twins below drive
# the same checker either way.  The derandomized "ci" profile and the
# no-hypothesis shim live in hypothesis_compat.
from hypothesis_compat import given, st  # noqa: E402


DEFAULT = policies.FreqCaPolicy(interval=5)
# deliberately includes compatible static families: taylorseer(5) keys
# with freqca(5) — same (interval, needed_history) — and fora(1) keys
# with none (both activate every step)
POLICIES = [
    None,                                            # -> engine default
    policies.TaylorSeerPolicy(interval=5),           # same key as DEFAULT
    policies.ForaPolicy(interval=2),
    policies.ForaPolicy(interval=1),                 # same key as "none"
    policies.NoCachePolicy(interval=1),
    policies.FreqCaAdaptivePolicy(tea_threshold=0.3, rho=0.25),
    policies.TeaCachePolicy(tea_threshold=0.2),
]
# multi-resolution streams: (latent, CRF) shape pairs a request may
# declare; None = undeclared (the engine-default pseudo-shape)
SHAPES = [
    None,
    ((8, 8, 4), (16, 64)),
    ((16, 16, 4), (64, 64)),
    ((32, 32, 4), (256, 64)),
]


@dataclasses.dataclass
class Cut:
    plan: object
    # request_ids lapsed anywhere in the queue at cut time, queue order
    lapsed_before: list
    queue_before: list          # request_ids queued at cut time


def drive(actions, max_batch, max_wait_s, grouped, pad_to_max=False):
    """Replay a generated action stream; return (submitted, cuts, sched).

    ``actions``: sequence of ("submit", gap_s, policy_idx, deadline_s)
    — optionally with a trailing shape index into ``SHAPES`` — and
    ("cut", gap_s) tuples, on a fake monotonically advancing clock;
    the stream always ends with a flush drain (every queue empties).
    """
    t = [0.0]
    sched = Scheduler(max_batch=max_batch, max_wait_s=max_wait_s,
                      pad_to_max=pad_to_max, clock=lambda: t[0],
                      group_policies=grouped, default_policy=DEFAULT)
    submitted, cuts, rid = [], [], 0

    def attempt(flush):
        lapsed = [sched.queue[i].request_id for i in sched._lapsed(t[0])]
        queued = [r.request_id for r in sched.queue]
        plan = sched.form_batch(flush=flush)
        if plan is not None:
            cuts.append(Cut(plan=plan, lapsed_before=lapsed,
                            queue_before=queued))
        return plan

    for act in actions:
        t[0] += act[1]
        if act[0] == "submit":
            shape = SHAPES[act[4]] if len(act) > 4 else None
            req = DiffusionRequest(request_id=rid, seed=rid,
                                   policy=POLICIES[act[2]],
                                   deadline_s=act[3],
                                   latent_shape=shape and shape[0],
                                   crf_shape=shape and shape[1])
            sched.submit(req)
            submitted.append(req)
            rid += 1
        else:
            attempt(flush=False)
    guard = 0
    while len(sched):
        assert attempt(flush=True) is not None   # flush always cuts
        guard += 1
        assert guard <= len(submitted), "drain did not terminate"
    return submitted, cuts, sched


def _plan_cut_key(plan, grouped):
    """The (shape, group) cut key a plan claims for itself."""
    shape = (None if plan.latent_shape is None
             else (tuple(plan.latent_shape), tuple(plan.crf_shape)))
    return (shape, plan.group_key if grouped else None)


def check_invariants(submitted, cuts, sched, max_batch, grouped,
                     pad_to_max=False):
    by_id = {r.request_id: r for r in submitted}
    # the scheduler's own (shape, group) cut key: purity, promotion,
    # and FIFO are all scoped to it (shape folds in unconditionally)
    key_of = {r.request_id: sched._cut_key(r) for r in submitted}

    # conservation: every submitted request served exactly once
    served = [r.request_id for c in cuts for r in c.plan.requests]
    assert sorted(served) == sorted(by_id), "dropped/duplicated requests"

    fifo_tail: dict = {}   # cut key -> last non-promoted rid served
    for c in cuts:
        ids = [r.request_id for r in c.plan.requests]
        plan_key = _plan_cut_key(c.plan, grouped)
        # shape purity in EVERY mode: one shape key per cut, and the
        # plan carries it
        assert {key_of[i] for i in ids} == {plan_key}, \
            f"impure cut {ids}: {[key_of[i] for i in ids]} != {plan_key}"
        if grouped:
            # canonical lane order: policy values in sorted blocks so
            # the jit signature keys on the composition, stable
            # submission order within each value
            vals = [repr(r.policy if r.policy is not None else DEFAULT)
                    for r in c.plan.requests]
            assert vals == sorted(vals), "lane order not canonical"
            last: dict = {}
            for v, i in zip(vals, ids, strict=True):
                assert last.get(v, -1) < i, "FIFO broken within value"
                last[v] = i
        else:
            # ungrouped batches list members in stable submission order
            assert ids == sorted(ids)
        # bucketing: a ladder signature that fits the real lanes
        assert c.plan.bucket in bucket_sizes(max_batch)
        want = (max_batch if pad_to_max
                else bucket_for(len(ids), max_batch))
        assert c.plan.bucket == want

        if grouped:
            # policy purity: one compatibility group per batch
            keys = {sched.group_key(by_id[i]) for i in ids}
            assert keys == {c.plan.group_key}, \
                f"mixed-policy batch under grouping: {keys}"

        # deadline promotion: a cut taken while lapsed requests exist
        # comes from the most-overdue request's (shape, group) and
        # contains ITS lapsed members up to max_batch — promotion never
        # leaks a lapsed request into a cut of another shape or group
        if c.lapsed_before:
            now = c.plan.formed_at
            overdue = {i: now - by_id[i].submit_time - by_id[i].deadline_s
                       for i in c.lapsed_before}
            worst = max(overdue.values())
            worst_keys = {key_of[i] for i, v in overdue.items()
                          if v == worst}
            assert plan_key in worst_keys, \
                f"cut {plan_key} ignored most-overdue {worst_keys}"
            in_group = [i for i in c.lapsed_before
                        if key_of[i] == plan_key]
            expect = in_group[:min(len(in_group), max_batch)]
            assert set(expect) <= set(ids), \
                f"lapsed {expect} missing from the next cut {ids}"

        # stable FIFO within a (shape, group) ACROSS cuts: a
        # non-promoted request is never served in a later cut than a
        # younger one of its own cut key (promoted = lapsed at its cut
        # time; lanes inside one cut run simultaneously, so canonical
        # lane order is exempt)
        non_promoted = [i for i in ids if i not in c.lapsed_before]
        for i in non_promoted:
            assert fifo_tail.get(plan_key, -1) < i, \
                f"request {i} overtook FIFO order in {plan_key}"
        for i in non_promoted:
            fifo_tail[plan_key] = max(fifo_tail.get(plan_key, -1), i)


def run_case(actions, max_batch, max_wait_s, grouped, pad_to_max=False):
    submitted, cuts, sched = drive(actions, max_batch, max_wait_s,
                                   grouped, pad_to_max)
    check_invariants(submitted, cuts, sched, max_batch, grouped,
                     pad_to_max)
    return submitted, cuts


# ---------------------------------------------------------------------------
# hypothesis property suite (the CI job)
# ---------------------------------------------------------------------------

def _actions():
    gap = st.floats(min_value=0.0, max_value=0.3, allow_nan=False,
                    allow_infinity=False)
    deadline = st.one_of(st.none(),
                         st.floats(min_value=0.0, max_value=0.5,
                                   allow_nan=False, allow_infinity=False))
    # every submit carries a shape index too (0 = undeclared), so the
    # whole property suite runs over (batch, seq)-mixed streams
    submit = st.tuples(st.just("submit"), gap,
                       st.integers(0, len(POLICIES) - 1), deadline,
                       st.integers(0, len(SHAPES) - 1))
    cut = st.tuples(st.just("cut"), gap)
    return st.lists(st.one_of(submit, cut), min_size=1, max_size=48)


@given(_actions(), st.integers(1, 8), st.sampled_from([0.0, 0.05, 1e9]),
       st.booleans())
def test_invariants_hold_for_arbitrary_streams(actions, max_batch,
                                               max_wait_s, grouped):
    """The full invariant set, grouped and ungrouped, any stream."""
    run_case(actions, max_batch, max_wait_s, grouped)


@given(_actions(), st.integers(1, 8))
def test_invariants_hold_with_pad_to_max(actions, max_batch):
    run_case(actions, max_batch, max_wait_s=0.01, grouped=True,
             pad_to_max=True)


@given(_actions(), st.integers(1, 4))
def test_grouped_and_ungrouped_serve_identical_request_sets(actions,
                                                            max_batch):
    """Grouping changes batch composition, never the served set."""
    sub_g, cuts_g = run_case(actions, max_batch, 0.05, grouped=True)
    sub_u, cuts_u = run_case(actions, max_batch, 0.05, grouped=False)
    assert sorted(r.request_id for c in cuts_g for r in c.plan.requests) \
        == sorted(r.request_id for c in cuts_u for r in c.plan.requests)


# ---------------------------------------------------------------------------
# deterministic twins (run in the bare tier-1 env, no hypothesis)
# ---------------------------------------------------------------------------

def _mixed_stream_actions():
    acts = []
    for i in range(16):
        acts.append(("submit", 0.01, i % len(POLICIES),
                     0.2 if i % 5 == 4 else None))
        if i % 3 == 2:
            acts.append(("cut", 0.05))
    acts.append(("cut", 1.0))
    return acts


@pytest.mark.parametrize("grouped", [False, True])
@pytest.mark.parametrize("max_batch", [1, 3, 4])
def test_deterministic_mixed_stream(grouped, max_batch):
    run_case(_mixed_stream_actions(), max_batch, max_wait_s=0.05,
             grouped=grouped)


def test_deterministic_pad_to_max():
    run_case(_mixed_stream_actions(), 4, max_wait_s=0.0, grouped=True,
             pad_to_max=True)


def test_deterministic_deadline_burst():
    """Lapsed requests across *different* groups: each is promoted into
    the very next cut of its group, most-overdue group first."""
    acts = [("submit", 0.0, 1, None), ("submit", 0.0, 2, None),
            ("submit", 0.0, 2, 0.10),       # fora(2): lapses second
            ("submit", 0.0, 5, 0.05),       # freqca_a: most overdue
            ("cut", 0.2)]
    submitted, cuts = run_case(acts, 8, max_wait_s=1e9, grouped=True)
    # first cut: the most-overdue lapsed request's (adaptive) group
    assert [r.request_id for r in cuts[0].plan.requests] == [3]
    # second: the other lapsed group, its lapsed member promoted
    assert [r.request_id for r in cuts[1].plan.requests] == [1, 2]


def test_deterministic_rare_group_not_starved():
    """A busy group keeps its bucket full; the rare policy's request is
    served as soon as it heads the queue and ages past max_wait."""
    acts = [("submit", 0.0, 5, None)]                 # rare adaptive
    acts += [("submit", 0.0, 2, None)] * 8            # busy fora group
    acts += [("cut", 0.0)]                            # full-bucket cut
    acts += [("submit", 0.0, 2, None)] * 4            # keeps arriving
    acts += [("cut", 0.2)]                            # rare head aged
    submitted, cuts = run_case(acts, 4, max_wait_s=0.1, grouped=True)
    # cut 1 at t=0: fora bucket full, rare head still young -> fora
    assert all(r.request_id != 0 for r in cuts[0].plan.requests)
    # cut 2 at t=0.2: age pressure -> the rare request's own group
    assert [r.request_id for r in cuts[1].plan.requests] == [0]


def test_deterministic_static_families_share_batches():
    """taylorseer(5)/freqca(5) and fora(1)/none key together: one batch
    each, never one per distinct policy object."""
    acts = [("submit", 0.0, 0, None), ("submit", 0.0, 1, None),
            ("submit", 0.0, 3, None), ("submit", 0.0, 4, None),
            ("cut", 0.2)]
    submitted, cuts = run_case(acts, 8, max_wait_s=0.05, grouped=True)
    assert len(cuts) == 2
    assert [r.request_id for r in cuts[0].plan.requests] == [0, 1]
    assert [r.request_id for r in cuts[1].plan.requests] == [2, 3]


# ---------------------------------------------------------------------------
# multi-resolution deterministic twins
# ---------------------------------------------------------------------------

def _multishape_stream_actions():
    """Shapes and policies both cycling, deadlines sprinkled in — the
    mixed-resolution production stream in miniature."""
    acts = []
    for i in range(20):
        acts.append(("submit", 0.01, i % len(POLICIES),
                     0.2 if i % 5 == 4 else None, i % len(SHAPES)))
        if i % 3 == 2:
            acts.append(("cut", 0.05))
    acts.append(("cut", 1.0))
    return acts


@pytest.mark.parametrize("grouped", [False, True])
@pytest.mark.parametrize("max_batch", [1, 3, 4])
def test_deterministic_multishape_stream(grouped, max_batch):
    """Full invariant set — shape purity included — over a stream that
    mixes four shapes with seven policies, in BOTH formation modes
    (shape purity is unconditional, not a grouping feature)."""
    run_case(_multishape_stream_actions(), max_batch, max_wait_s=0.05,
             grouped=grouped)


@pytest.mark.parametrize("grouped", [False, True])
def test_deterministic_shape_purity_same_policy(grouped):
    """Identical policies at two shapes never share a cut: the shape
    key alone forces separate batches."""
    acts = [("submit", 0.0, 2, None, 1), ("submit", 0.0, 2, None, 2),
            ("submit", 0.0, 2, None, 1), ("cut", 0.2)]
    submitted, cuts = run_case(acts, 8, max_wait_s=0.05, grouped=grouped)
    assert len(cuts) == 2
    assert [r.request_id for r in cuts[0].plan.requests] == [0, 2]
    assert [r.request_id for r in cuts[1].plan.requests] == [1]
    assert cuts[0].plan.latent_shape == SHAPES[1][0]
    assert cuts[1].plan.latent_shape == SHAPES[2][0]


def test_deterministic_no_cross_shape_promotion():
    """A lapsed small-shape request is promoted into its own shape's
    next cut — never pulled into the large-shape batch that triggers
    first, and never starved behind it."""
    # same policy everywhere: only the shape key separates the lanes
    acts = [("submit", 0.0, 2, None, 2)] * 0
    acts = [("submit", 0.0, 2, 0.05, 1),    # small shape, tight deadline
            ("submit", 0.0, 2, None, 2),
            ("submit", 0.0, 2, None, 2),
            ("cut", 0.2),                   # deadline lapsed -> shape 1
            ("cut", 0.0)]                   # then the shape-2 backlog
    submitted, cuts = run_case(acts, 8, max_wait_s=1e9, grouped=True)
    assert [r.request_id for r in cuts[0].plan.requests] == [0]
    assert cuts[0].plan.latent_shape == SHAPES[1][0]
    assert [r.request_id for r in cuts[1].plan.requests] == [1, 2]
    assert cuts[1].plan.latent_shape == SHAPES[2][0]


def test_deterministic_partial_shape_declaration():
    """A request declaring only its latent shape resolves to the unique
    ladder entry matching it (scheduler built with a ladder), and cuts
    stay shape-pure."""
    from repro.serving.scheduler import Scheduler as S
    ladder = {SHAPES[1], SHAPES[2]}
    sched = S(max_batch=4, max_wait_s=0.0, clock=lambda: 0.0,
              default_shape=SHAPES[1], allowed_shapes=set(ladder))
    sched.submit(DiffusionRequest(request_id=0, seed=0,
                                  latent_shape=SHAPES[2][0]), now=0.0)
    sched.submit(DiffusionRequest(request_id=1, seed=1), now=0.0)
    plan = sched.form_batch(now=1.0)
    # the partially-declared request completed to the full SHAPES[2]
    # pair and therefore cannot share the default-shape cut
    assert [r.request_id for r in plan.requests] == [0]
    assert plan.latent_shape == SHAPES[2][0]
    assert plan.crf_shape == SHAPES[2][1]
    plan2 = sched.form_batch(now=1.0)
    assert [r.request_id for r in plan2.requests] == [1]
    assert plan2.latent_shape == SHAPES[1][0]
