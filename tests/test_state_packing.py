"""Flat-buffer small-leaf state packing (runtime/state_packing.py).

The packed step must be bit-identical to the plain step: packing is pure
storage plumbing (the TPU analog of the reference's flat-params design —
upstream ``MultiLayerNetwork.init()`` flattening; SURVEY.md §3.1).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn import DenseLayer, InputType, OutputLayer
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.runtime.environment import get_environment
from deeplearning4j_tpu.runtime.state_packing import LeafPacker
from deeplearning4j_tpu.train.fit_engine import PackedStepLoop
from deeplearning4j_tpu.train.updaters import Adam


def _tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _make_net(seed=7):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(DenseLayer(n_out=24, activation="tanh"))
            .layer(OutputLayer(n_out=5, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(12))
            .build())
    from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
    return MultiLayerNetwork(conf).init()


class TestLeafPacker:
    def test_roundtrip_identity(self):
        tree = {
            "a": {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((7,))},
            "big": jnp.zeros((600, 600)),  # > 1 MB, stays standalone
            "c": [jnp.full((3,), 2, jnp.int32), jnp.float32(5.0)],
        }
        packer = LeafPacker(tree)
        packed = packer.pack(tree)
        _tree_equal(packer.unpack(packed), tree)
        # big leaf kept standalone; small ones packed per dtype
        assert packer.n_kept == 1
        assert packer.n_packed == 4

    def test_scalar_and_alignment(self):
        tree = {"s": jnp.int32(3), "v": jnp.arange(5.0)}
        packer = LeafPacker(tree, align=8)
        _tree_equal(packer.unpack(packer.pack(tree)), tree)

    def test_structure_mismatch_raises(self):
        tree = {"a": jnp.ones((3,))}
        packer = LeafPacker(tree)
        with pytest.raises(ValueError):
            packer.pack({"a": jnp.ones((3,)), "b": jnp.ones((2,))})

    def test_dtype_mismatch_raises(self):
        tree = {"a": jnp.ones((3,), jnp.float32)}
        packer = LeafPacker(tree)
        with pytest.raises(ValueError, match="rebuild the packer"):
            packer.pack({"a": jnp.ones((3,), jnp.bfloat16)})

    def test_handle_count_reduction(self):
        net = _make_net()
        packer = LeafPacker(net.train_state)
        packed = packer.pack(net.train_state)
        n_packed = len(jax.tree_util.tree_leaves(packed))
        n_plain = len(jax.tree_util.tree_leaves(net.train_state))
        assert n_packed < n_plain  # every small leaf collapsed into buffers


class TestPackedStepEquivalence:
    @pytest.mark.quick
    def test_packed_step_bit_identical(self):
        """N packed steps == N plain steps, bitwise, same seeds."""
        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 12)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 16)]

        net_a = _make_net()
        net_b = _make_net()
        _tree_equal(net_a.train_state, net_b.train_state)

        step_a = net_a._jitted("train_step", net_a._make_train_step)
        step_b, packer = net_b._jitted_packed()
        ts = net_a.train_state
        pts = packer.pack_device(net_b.train_state)
        key = jax.random.PRNGKey(3)
        for i in range(4):
            k = jax.random.fold_in(key, i)
            ts, loss_a = step_a(ts, x, y, k, None, None)
            pts, loss_b = step_b(pts, x, y, k, None, None)
            assert float(loss_a) == float(loss_b)
        _tree_equal(ts, packer.unpack_device(pts))

    def test_fit_equivalence_packed_vs_unpacked(self):
        """fit() with packing on vs off: identical final params."""
        rng = np.random.default_rng(1)
        x = rng.normal(size=(32, 12)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 32)]
        env = get_environment()
        prev = env.packed_state
        try:
            env.set_packed_state(True)
            net_on = _make_net().fit(x, y, epochs=3)
            env.set_packed_state(False)
            net_off = _make_net().fit(x, y, epochs=3)
        finally:
            env.packed_state = prev
        _tree_equal(net_on.train_state.params, net_off.train_state.params)
        _tree_equal(net_on.train_state.opt_state, net_off.train_state.opt_state)

    def test_fit_graph_packed(self):
        """ComputationGraph fit with packing: state stays consistent."""
        from deeplearning4j_tpu.nn.graph_vertices import ElementWiseVertex
        g = (NeuralNetConfiguration.builder()
             .seed(5)
             .updater(Adam(1e-2))
             .graph_builder()
             .add_inputs("in"))
        g.add_layer("d1", DenseLayer(n_out=16, activation="relu"), "in")
        g.add_layer("d2", DenseLayer(n_out=16, activation="relu"), "d1")
        g.add_vertex("add", ElementWiseVertex(op="add"), "d1", "d2")
        g.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                       loss="mcxent"), "add")
        g.set_outputs("out")
        from deeplearning4j_tpu.nn.inputs import InputType
        g.set_input_types(InputType.feed_forward(8))
        env = get_environment()
        prev = env.packed_state
        rng = np.random.default_rng(2)
        x = rng.normal(size=(20, 8)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 20)]
        try:
            env.set_packed_state(True)
            from deeplearning4j_tpu.models.computation_graph import ComputationGraph
            cg_on = ComputationGraph(g.build()).init().fit(x, y, epochs=2)
            env.set_packed_state(False)
            cg_off = ComputationGraph(g.build()).init().fit(x, y, epochs=2)
        finally:
            env.packed_state = prev
        _tree_equal(cg_on.train_state.params, cg_off.train_state.params)

    def test_stateful_listener_disables_packing(self):
        from deeplearning4j_tpu.train.listeners import TrainingListener

        class Grabby(TrainingListener):
            def __init__(self):
                self.seen_steps = []

            def iteration_done(self, model, iteration, epoch, score):
                # must see a FRESH train_state every iteration
                self.seen_steps.append(int(model.train_state.step))

        net = _make_net()
        lst = Grabby()
        net.set_listeners(lst)
        rng = np.random.default_rng(3)
        x = rng.normal(size=(8, 12)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 8)]
        net.fit(x, y, epochs=3)
        assert lst.seen_steps == [1, 2, 3]

    def test_stateful_listener_also_disables_grouping(self):
        """dispatch_unroll>1 + a state-reading listener: batches must still
        dispatch one at a time so iteration_done observes per-iteration
        state (grouping would show iteration 1 the weights of iteration K)."""
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.data.iterators import ListDataSetIterator
        from deeplearning4j_tpu.train.listeners import TrainingListener

        class Grabby(TrainingListener):
            def __init__(self):
                self.seen_steps = []

            def iteration_done(self, model, iteration, epoch, score):
                self.seen_steps.append(int(model.train_state.step))

        env = get_environment()
        prev = env.dispatch_unroll
        rng = np.random.default_rng(7)
        x = rng.normal(size=(8, 12)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 8)]
        try:
            env.set_dispatch_unroll(4)
            net = _make_net()
            lst = Grabby()
            net.set_listeners(lst)
            it = ListDataSetIterator([DataSet(x, y) for _ in range(4)],
                                     batch_size=8)
            net.fit(it, epochs=1)
        finally:
            env.dispatch_unroll = prev
        assert lst.seen_steps == [1, 2, 3, 4]

    def test_stateless_listener_keeps_packing(self):
        from deeplearning4j_tpu.train.listeners import CollectScoresListener
        net = _make_net()
        scores = CollectScoresListener()
        net.set_listeners(scores)
        rng = np.random.default_rng(4)
        x = rng.normal(size=(8, 12)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 8)]
        net.fit(x, y, epochs=2)
        assert len(scores.scores) == 2
        # state is fresh after fit returns
        assert int(net.train_state.step) == 2


class TestPackedFitRobustness:
    def test_exception_mid_fit_preserves_progress(self):
        """An iterator error mid-fit must not lose completed packed steps."""
        from deeplearning4j_tpu.data.dataset import DataSet

        rng = np.random.default_rng(5)
        x = rng.normal(size=(8, 12)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 8)]

        class ExplodingIterator:
            def __init__(self, n_good):
                self.n_good = n_good
                self._i = 0

            def reset(self):
                self._i = 0

            def __iter__(self):
                return self

            def __next__(self):
                if self._i >= self.n_good:
                    raise RuntimeError("data source died")
                self._i += 1
                return DataSet(x, y)

        net = _make_net()
        with pytest.raises(RuntimeError, match="data source died"):
            net.fit(ExplodingIterator(3), epochs=1)
        # the three completed steps survive the exception
        assert int(net.train_state.step) == 3


class TestDispatchUnroll:
    def _data(self, n_batches, seed=9):
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.data.iterators import ListDataSetIterator
        rng = np.random.default_rng(seed)
        batches = [DataSet(rng.normal(size=(8, 12)).astype(np.float32),
                           np.eye(5, dtype=np.float32)[rng.integers(0, 5, 8)])
                   for _ in range(n_batches)]
        return ListDataSetIterator(batches, batch_size=8)

    def test_unrolled_fit_bit_identical(self):
        """fit with dispatch_unroll=3 (incl. a partial tail group) must match
        the per-batch loop bitwise, including the listener loss sequence."""
        from deeplearning4j_tpu.train.listeners import CollectScoresListener
        env = get_environment()
        prev = env.dispatch_unroll
        try:
            nets, scores = [], []
            for k in (1, 3):
                env.set_dispatch_unroll(k)
                net = _make_net()
                coll = CollectScoresListener()
                net.set_listeners(coll)
                net.fit(self._data(7), epochs=2)  # 7 % 3 != 0: partial tail
                nets.append(net)
                scores.append([s for _, s in coll.scores])
        finally:
            env.dispatch_unroll = prev
        assert len(scores[0]) == len(scores[1]) == 14
        np.testing.assert_allclose(scores[0], scores[1], rtol=0, atol=0)
        _tree_equal(nets[0].train_state.params, nets[1].train_state.params)
        assert int(nets[1].train_state.step) == 14

    def test_exception_mid_fit_with_unroll_preserves_buffered(self):
        """Iterator death mid-epoch with dispatch_unroll>1: batches buffered
        before the exception must still train (flush in the finally)."""
        from deeplearning4j_tpu.data.dataset import DataSet

        rng = np.random.default_rng(6)
        x = rng.normal(size=(8, 12)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 8)]

        class ExplodingIterator:
            def __init__(self, n_good):
                self.n_good, self._i = n_good, 0

            def reset(self):
                self._i = 0

            def __iter__(self):
                return self

            def __next__(self):
                if self._i >= self.n_good:
                    raise RuntimeError("died")
                self._i += 1
                return DataSet(x, y)

        env = get_environment()
        prev = env.dispatch_unroll
        try:
            env.set_dispatch_unroll(4)
            net = _make_net()
            with pytest.raises(RuntimeError, match="died"):
                net.fit(ExplodingIterator(3), epochs=1)  # 3 < unroll: all buffered
        finally:
            env.dispatch_unroll = prev
        assert int(net.train_state.step) == 3

    def test_unroll_with_packing_disabled_falls_back(self):
        env = get_environment()
        prev_u, prev_p = env.dispatch_unroll, env.packed_state
        try:
            env.set_dispatch_unroll(4)
            env.set_packed_state(False)
            net = _make_net().fit(self._data(5), epochs=1)
        finally:
            env.dispatch_unroll, env.packed_state = prev_u, prev_p
        assert int(net.train_state.step) == 5

    def test_raising_listener_does_not_double_train(self):
        """A listener that raises mid-group must not cause the finally-flush
        to re-dispatch already-executed batches (verified-by-execution bug:
        the group trained twice)."""
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.data.iterators import ListDataSetIterator
        from deeplearning4j_tpu.train.listeners import TrainingListener

        class RaiseOnFirst(TrainingListener):
            needs_model_state = False

            def __init__(self):
                self.calls = 0

            def iteration_done(self, model, iteration, epoch, score):
                self.calls += 1
                if self.calls == 1:
                    raise RuntimeError("listener boom")

        rng = np.random.default_rng(8)
        x = rng.normal(size=(8, 12)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 8)]
        env = get_environment()
        prev = env.dispatch_unroll
        try:
            env.set_dispatch_unroll(2)
            net = _make_net()
            lst = RaiseOnFirst()
            net.set_listeners(lst)
            it = ListDataSetIterator([DataSet(x, y) for _ in range(2)],
                                     batch_size=8)
            with pytest.raises(RuntimeError, match="listener boom"):
                net.fit(it, epochs=1)
        finally:
            env.dispatch_unroll = prev
        # the 2-batch group ran ONCE: step counter is 2, not 4
        assert int(net.train_state.step) == 2

    def test_graph_unrolled_fit_matches_single(self):
        """ComputationGraph fit with dispatch_unroll=3 == per-batch loop."""
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.data.iterators import ListDataSetIterator
        from deeplearning4j_tpu.models.computation_graph import ComputationGraph
        from deeplearning4j_tpu.nn.graph_vertices import ElementWiseVertex

        def build():
            g = (NeuralNetConfiguration.builder().seed(13).updater(Adam(1e-2))
                 .graph_builder().add_inputs("in"))
            g.add_layer("d1", DenseLayer(n_out=16, activation="relu"), "in")
            g.add_layer("d2", DenseLayer(n_out=16, activation="relu"), "d1")
            g.add_vertex("add", ElementWiseVertex(op="add"), "d1", "d2")
            g.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                           loss="mcxent"), "add")
            g.set_outputs("out")
            g.set_input_types(InputType.feed_forward(8))
            return ComputationGraph(g.build()).init()

        rng = np.random.default_rng(12)
        batches = [DataSet(rng.normal(size=(10, 8)).astype(np.float32),
                           np.eye(3, dtype=np.float32)[rng.integers(0, 3, 10)])
                   for _ in range(7)]
        env = get_environment()
        prev = env.dispatch_unroll
        try:
            nets = []
            for k in (1, 3):
                env.set_dispatch_unroll(k)
                net = build()
                net.fit(ListDataSetIterator(list(batches), batch_size=10),
                        epochs=2)
                nets.append(net)
        finally:
            env.dispatch_unroll = prev
        _tree_equal(nets[0].train_state.params, nets[1].train_state.params)
        assert int(nets[1].train_state.step) == 14
