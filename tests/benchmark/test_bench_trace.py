"""The trace reduction: on hand-made traces with known answers, and on the
small recorded v5e trace under ``benchmark/testdata/``."""

import pytest
from jax.profiler import ProfileData

from benchmark import trace_reduce as tr

from bench_tiny import BENCH

HAND_MADE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 11 offset_ps: 12000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 stats { metadata_id: 1 str_value: "jit(train_round)/jit(main)/murmura.train/while/body/dot_general" } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused.3" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.9" } }
  event_metadata { key: 10 value { id: 10 name: "jit_train_round(123)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_eval_step(456)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python3/77" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 9500000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 14000000 duration_ps: 6500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "stage_inputs" } }
  event_metadata { key: 2 value { id: 2 name: "TransferFromDevice" } }
}
"""

HLO = [
    'HloModule jit_train_round, entry_computation_layout={()}\n'
    '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_round)/jit(main)/murmura.aggregate/sort" source_file="x.py"}\n'
    '  ROOT %copy.9 = f32[8]{0} copy(%fusion.3), metadata={op_name="jit(train_round)/jit(main)/murmura.exchange/add"}\n',
    'HloModule jit_eval_step\n'
    '  fusion.3 = f32[8]{0} fusion(p), kind=kLoop, metadata={op_name="jit(eval_step)/murmura.eval/dot"}\n',
]


def hand_made():
    return ProfileData.from_text_proto(HAND_MADE)


def test_intervals():
    assert tr.union_length([(0, 5), (3, 8), (10, 12), (11, 11)]) == 10
    assert tr.gaps([(0, 5), (3, 8), (10, 12)]) == [(8, 10)]
    assert tr.union_length([]) == 0 and tr.gaps([(1, 2)]) == []


def test_op_id_takes_the_name_out_of_a_whole_instruction():
    whole = "%fusion.419 = u32[128]{0:T(128)S(1)} fusion(), kind=kLoop, calls=%fused.7"
    assert tr.op_id(whole) == tr.op_id("%fusion.419") == tr.op_id("fusion.419") == "fusion.419"


def test_scope_of_takes_the_murmura_part():
    assert tr.scope_of(["%fusion.1", "jit(f)/jit(main)/murmura.train/while/dot"]) == (
        "murmura.train"
    )
    assert tr.scope_of(['op_name="a/murmura.aggregate"']) == "murmura.aggregate"
    assert tr.scope_of(["jit(f)/add"]) is None


def test_chain_of_keeps_every_label_outermost_first():
    inner = "jit(f)/jit(main)/murmura.train/while/body/murmura.attention/dot_general"
    assert tr.chain_of(["%fusion.1", inner]) == "murmura.train/murmura.attention"
    assert tr.scope_of([inner]) == "murmura.train"
    assert tr.chain_of(['op_name="a/murmura.aggregate"']) == "murmura.aggregate"
    assert tr.chain_of(["jit(f)/add"]) is None
    scopes = tr.scope_map_from_hlo([
        'HloModule jit_f\n  %body.dot = f32[8]{0} dot(%a, %b), '
        f'metadata={{op_name="{inner}"}}\n'])
    assert scopes["jit_f"] == {"body.dot": "murmura.train/murmura.attention"}


@pytest.mark.parametrize("op_name,chain", [
    ("jit(f)/murmura.x/transpose(jvp(murmura.x))/mul", "murmura.x"),
    ("jit(f)/jvp(murmura.train)/while/body/murmura.train/murmura.router/dot",
     "murmura.train/murmura.router"),
    ("jit(f)/transpose(jvp(murmura.train))/while/body/checkpoint/murmura.a/"
     "rematted_computation/murmura.a/tanh", "murmura.train/murmura.a"),
    ("jit(f)/murmura.a/murmura.b/murmura.a/add", "murmura.a/murmura.b/murmura.a"),
])
def test_chain_of_counts_a_label_that_a_transform_repeats_once(op_name, chain):
    assert tr.chain_of([op_name]) == chain
    assert tr.scope_of([op_name]) == chain.split("/")[0]


def test_chain_of_on_a_lowered_gradient_through_a_loop():
    """The labels as JAX writes them: a gradient taken outside the outer
    scope, the inner one in a rematerialised ``scan`` body, so that the
    labels come wrapped in ``jvp(..)``, ``transpose(jvp(..))`` and under
    ``checkpoint/rematted_computation``."""
    import jax
    import jax.numpy as jnp

    def loss(w, x):
        with jax.named_scope("murmura.outer"):
            def body(h, _):
                with jax.named_scope("murmura.inner"):
                    h = jnp.tanh(h) * 2.0
                return h, None

            h, _ = jax.lax.scan(jax.checkpoint(body), x @ w, None, length=3)
            return jnp.sum(h * h)

    text = jax.jit(jax.grad(loss)).lower(
        jnp.ones((4, 4)), jnp.ones((3, 4))).compile().as_text()
    chains = {c for ops in tr.scope_map_from_hlo([text]).values() for c in ops.values()}
    assert chains == {"murmura.outer", "murmura.outer/murmura.inner"}


def test_scope_map_is_kept_per_program():
    scopes = tr.scope_map_from_hlo(HLO)
    assert scopes["jit_train_round"] == {
        "fusion.3": "murmura.aggregate", "copy.9": "murmura.exchange",
    }
    assert scopes["jit_eval_step"] == {"fusion.3": "murmura.eval"}


def test_reduction_of_a_hand_made_trace():
    red = tr.reduce_space(hand_made(), tr.scope_map_from_hlo(HLO))
    assert red.devices == 1
    # Busy: [0,5] + [7,9] + [12,14] + [20,21] us = 10 of a 21 us window;
    # the child %fusion.7 lies inside %while.1 and adds nothing.
    assert red.busy_s == pytest.approx(10e-6) and red.window_s == pytest.approx(21e-6)
    assert not any("fusion.7" in k for k in red.op_s)
    # %while.1 names its scope itself; %fusion.3 runs once in each program
    # and is joined through the program that was running; %copy.9 ran
    # outside any program and only one program has such an operation.
    assert red.scope_s == pytest.approx({
        "murmura.train": 5e-6, "murmura.aggregate": 2e-6, "murmura.eval": 2e-6,
        "murmura.exchange": 1e-6,
    })
    assert red.unscoped_s == 0.0
    assert red.program_s == pytest.approx(
        {"jit_train_round(123)": 9e-6, "jit_eval_step(456)": 2e-6}
    )
    gaps = dict(red.idle_gaps)
    assert gaps["python3: TransferFromDevice"] == pytest.approx(6e-6)
    assert gaps["python3: stage_inputs"] == pytest.approx(3e-6)
    top = red.breakdown(top=2)
    assert top["device_ops"][0] == ["%while.1 [murmura.train]", pytest.approx(5e-6)]
    assert len(top["device_ops"]) == 2 and len(top["idle_gaps"]) == 2


def test_without_a_join_operations_stay_unscoped():
    red = tr.reduce_space(hand_made())
    assert red.scope_s == pytest.approx({"murmura.train": 5e-6})
    assert red.unscoped_s == pytest.approx(5e-6)


def test_a_trace_without_a_device_reduces_to_nothing(tmp_path):
    assert tr.reduce_dir(str(tmp_path)).devices == 0


def test_cut_to_text_round_trips():
    scopes = tr.scope_map_from_hlo(HLO)
    text = tr.cut_to_text(hand_made(), rounds=1, scope_map=scopes, host_min_ns=0)
    again = tr.reduce_space(ProfileData.from_text_proto(text))
    # One program ran once only: the cut ends where it started again, or,
    # as here, at its one start: nothing is inside.
    assert again.devices == 0
    twice = HAND_MADE.replace(
        "events { metadata_id: 11 offset_ps: 12000000 duration_ps: 2000000 }",
        "events { metadata_id: 11 offset_ps: 12000000 duration_ps: 2000000 }\n"
        "    events { metadata_id: 10 offset_ps: 15000000 duration_ps: 1000000 }",
    )
    text = tr.cut_to_text(ProfileData.from_text_proto(twice), rounds=1,
                          scope_map=scopes, host_min_ns=0)
    again = tr.reduce_space(ProfileData.from_text_proto(text))
    # Up to the second run of the first program, 15 us in: the last
    # operation is outside; the scopes travel with the events.
    assert again.busy_s == pytest.approx(9e-6)
    assert again.scope_s == pytest.approx({
        "murmura.train": 5e-6, "murmura.aggregate": 2e-6, "murmura.eval": 2e-6,
    })


def test_reduction_of_the_recorded_v5e_round():
    """``testdata/sg_round.xspace.txt``: the first round of the traced
    window of ``cnn_sketchguard_er_n64`` on a TPU v5 lite (PR 25, seed
    2147486123), cut by ``trace_reduce.py <dir> --cut 1``.  The whole
    traced window of that run, eight rounds, read (seconds a round):
    aggregate 2.23569, train 0.09036, exchange 0.01008, eval 0.00625."""
    text = (BENCH / "testdata" / "sg_round.xspace.txt").read_text()
    red = tr.reduce_space(ProfileData.from_text_proto(text))
    assert red.devices == 1
    assert red.busy_s == pytest.approx(2.352940882, rel=1e-9)
    assert red.window_s == pytest.approx(2.354386807, rel=1e-9)
    assert red.scope_s == pytest.approx({
        "murmura.aggregate": 2.235693046, "murmura.train": 0.090364556,
        "murmura.exchange": 0.010079381, "murmura.eval": 0.00624582,
    }, rel=1e-8)
    assert red.unscoped_s == pytest.approx(0.010558079, rel=1e-6)
    # The round against the run's eight: the same to a part in a thousand.
    for scope, per_round in (("murmura.aggregate", 2.23569), ("murmura.train", 0.09036),
                             ("murmura.exchange", 0.01008), ("murmura.eval", 0.00625)):
        assert red.scope_s[scope] == pytest.approx(per_round, rel=2e-3)
    assert sum(red.program_s.values()) == pytest.approx(2.352948391, rel=1e-8)
    top = red.breakdown(top=3)["device_ops"]
    assert "count_sketch_pallas" in top[0][0] and "[murmura.aggregate]" in top[0][0]
    # The idle share of this round: the readers' arithmetic.
    from benchmark.readers import host_gap, idle_share

    context = {"trace": red, "traced_rounds": 1}
    assert idle_share.read(context) == pytest.approx(100 * (1 - 2.352940882 / 2.354386807))
    assert host_gap.read(context) == pytest.approx((2.354386807 - 2.352940882) * 1e3)


NESTED = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 20000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 6000000 duration_ps: 0 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 12000000 duration_ps: 2000000 }
    events { metadata_id: 6 offset_ps: 16000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "%call.3" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5" } }
  event_metadata { key: 6 value { id: 6 name: "%copy.6" } }
  event_metadata { key: 10 value { id: 10 name: "jit_train_round(1)" } }
}
"""

NESTED_HLO = [
    'HloModule jit_train_round\n'
    '  %while.1 = (f32[8]) while(%t), metadata={op_name="jit(f)/murmura.train/while"}\n'
    '  %fusion.2 = f32[8]{0} fusion(%p), metadata={op_name="jit(f)/murmura.train/while/body/murmura.attention/dot"}\n'
    '  %fusion.4 = f32[8]{0} fusion(%p), metadata={op_name="jit(f)/murmura.train/while/body/murmura.router/top_k"}\n'
    '  %fusion.5 = f32[8]{0} fusion(%p), metadata={op_name="jit(f)/murmura.aggregate/add"}\n'
]


def test_the_innermost_table_keeps_what_lies_inside_an_outer_event():
    """%while.1 [0, 10) holds %fusion.2 [1, 4), %call.3 [4, 8) (no label of
    its own, two %fusion.4 of 1 us inside, and a marker of no length) and
    one %fusion.5 [8, 9) that carries another scope's label; outside run
    %fusion.5 [12, 14) and %copy.6 [16, 17) under no label."""
    red = tr.reduce_space(ProfileData.from_text_proto(NESTED),
                          tr.scope_map_from_hlo(NESTED_HLO))
    # The outermost table reads as it always did: the loop whole.
    assert red.scope_s == pytest.approx(
        {"murmura.train": 10e-6, "murmura.aggregate": 2e-6})
    assert red.unscoped_s == pytest.approx(1e-6)
    assert red.busy_s == pytest.approx(13e-6)
    assert red.leaf_s == pytest.approx({
        "murmura.train/murmura.attention": 3e-6,
        "murmura.train/murmura.router": 2e-6,
        "murmura.aggregate": 3e-6,
    })
    assert red.leaf_unscoped_s == pytest.approx(1e-6)
    from benchmark.readers import leaf_scope_ms

    context = {"trace": red, "traced_rounds": 2}
    read = lambda chain: leaf_scope_ms.read(context, chain)
    assert read("murmura.train") == pytest.approx(2.5e-3)  # both, per round
    assert read("murmura.train/murmura.router") == pytest.approx(1e-3)
    assert read("murmura.aggregate") == pytest.approx(1.5e-3)
    assert read("murmura.tra") is None and read("murmura.eval") is None
    # An operation without a label of its own has that of the event around it.
    unlabelled = [NESTED_HLO[0].replace("murmura.router/", "")]
    red = tr.reduce_space(ProfileData.from_text_proto(NESTED),
                          tr.scope_map_from_hlo(unlabelled))
    assert red.leaf_s["murmura.train"] == pytest.approx(2e-6)


def test_a_nested_cut_round_trips():
    scopes = tr.scope_map_from_hlo(NESTED_HLO)
    twice = NESTED.replace(
        "events { metadata_id: 10 offset_ps: 0 duration_ps: 20000000 }",
        "events { metadata_id: 10 offset_ps: 0 duration_ps: 15000000 }\n"
        "    events { metadata_id: 10 offset_ps: 15000000 duration_ps: 5000000 }")
    space = ProfileData.from_text_proto(twice)
    flat = tr.reduce_space(ProfileData.from_text_proto(
        tr.cut_to_text(space, rounds=1, scope_map=scopes, host_min_ns=0)))
    deep = tr.reduce_space(ProfileData.from_text_proto(
        tr.cut_to_text(space, rounds=1, scope_map=scopes, host_min_ns=0, nested=True)))
    assert flat.scope_s == deep.scope_s == pytest.approx(
        {"murmura.train": 10e-6, "murmura.aggregate": 2e-6})
    assert flat.leaf_s == pytest.approx(flat.scope_s)  # nothing inside was kept
    assert deep.leaf_s == pytest.approx({
        "murmura.train/murmura.attention": 3e-6,
        "murmura.train/murmura.router": 2e-6, "murmura.aggregate": 3e-6})


def test_the_outermost_table_reads_the_parents_digits():
    """``scope_s``, ``unscoped_s`` and ``busy_s`` of the recorded round, to
    the last digit as the reduction of commit ee733d0 gave them."""
    text = (BENCH / "testdata" / "sg_round.xspace.txt").read_text()
    red = tr.reduce_space(ProfileData.from_text_proto(text))
    assert red.scope_s == {
        "murmura.eval": 0.006245819999999999, "murmura.train": 0.090364556,
        "murmura.exchange": 0.010079381, "murmura.aggregate": 2.2356930459999993}
    assert red.unscoped_s == 0.010558079000000007 and red.busy_s == 2.352940882
    # That cut kept no inner event: its innermost table is its outermost.
    assert red.leaf_s == pytest.approx(red.scope_s, rel=1e-12)


def test_the_innermost_table_of_the_recorded_nested_round():
    """``testdata/sg_round_nested.xspace.txt``: the first traced round of
    ``cnn_sketchguard_er_n64`` on a TPU v5 lite with the operations inside
    the outer ones kept (``--cut 1 <file> nested``; the program of commit
    ee733d0, seed 2900000021, my chip run, PR 29).  The local-SGD loop is
    one outer event, ``%while.11``, with some 600 events inside; what
    the loop has beyond them is its own overhead."""
    text = (BENCH / "testdata" / "sg_round_nested.xspace.txt").read_text()
    red = tr.reduce_space(ProfileData.from_text_proto(text))
    assert red.devices == 1 and red.busy_s == pytest.approx(0.125155389, rel=1e-9)
    assert red.scope_s == pytest.approx({
        "murmura.train": 0.09045064, "murmura.aggregate": 0.013226981,
        "murmura.eval": 0.006166337, "murmura.exchange": 0.00474,
        "murmura.flatten": 0.00298428}, rel=1e-8)
    loop = dict(red.breakdown(top=1)["device_ops"])["%while.11 [murmura.train]"]
    assert loop == pytest.approx(0.090260048, rel=1e-9)
    inside = red.leaf_s["murmura.train"] - (red.scope_s["murmura.train"] - loop)
    assert 0.999 * loop < inside <= loop
    assert inside == pytest.approx(0.090252309, rel=1e-8)
    # The scopes without a loop read the same in both tables.
    for scope in ("murmura.aggregate", "murmura.eval", "murmura.exchange",
                  "murmura.flatten"):
        assert red.leaf_s[scope] == pytest.approx(red.scope_s[scope], rel=1e-12)
    assert red.leaf_unscoped_s == pytest.approx(red.unscoped_s, rel=1e-12)
    from benchmark.readers import leaf_scope_ms, scope_device_ms

    context = {"trace": red, "traced_rounds": 1}
    leaf = leaf_scope_ms.read(context, "murmura.train")
    outer = scope_device_ms.read(context, ["murmura.train"])
    assert 0.99 * outer < leaf < outer
