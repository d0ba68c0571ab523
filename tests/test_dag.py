"""Task DAG + priority list scheduling (Alg. 4.2) tests."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.dag import (TaskDAG, choose_fc_block, choose_oc_tile,
                            cnn_training_dag, conv_grid_tasks,
                            conv_layer_tasks, conv_output_shape,
                            fc_grid_tasks, priority_schedule)
from repro.kernels import LANE


class TestConvDecomposition:
    def test_eq12_output_shape(self):
        # (32 - 3 + 2*1)/1 + 1 = 32 (SAME-ish)
        assert conv_output_shape(32, 32, 3, 3, 1, 1) == (32, 32)
        assert conv_output_shape(28, 28, 5, 5, 1, 0) == (24, 24)

    def test_eq13_task_count(self):
        dag = TaskDAG()
        tids = conv_layer_tasks(dag, 8, 8, 3, 3, pad=1, tile=1)
        assert len(tids) == 8 * 8            # K_C = H_a * W_a

    def test_tiling_reduces_tasks(self):
        dag = TaskDAG()
        tids = conv_layer_tasks(dag, 8, 8, 3, 3, pad=1, tile=4)
        assert len(tids) == 4                # (8/4)^2


class TestExecutedGrid:
    """PT_Conv at pallas-grid granularity + the oc_tile cost model."""

    def test_grid_task_count_and_cost(self):
        dag = TaskDAG()
        tids = conv_grid_tasks(dag, batch=4, cout=16, oc_tile=8,
                               cost_per_channel=2.0)
        assert len(tids) == 4 * (16 // 8)
        assert all(dag.tasks[t].cost == 16.0 for t in tids)

    def test_grid_rejects_non_divisor(self):
        with pytest.raises(ValueError):
            conv_grid_tasks(TaskDAG(), batch=1, cout=16, oc_tile=5)

    def test_choose_tile_divides_cout(self):
        for batch, cout in [(1, 4), (2, 16), (8, 32), (3, 12), (16, 128)]:
            tile = choose_oc_tile(batch, cout)
            assert cout % tile == 0 and tile >= 1

    def test_small_cout_stays_untiled(self):
        # a narrow cout has no lane-legal split: one task per image
        assert choose_oc_tile(4, 4) == 4

    def test_wide_conv_tiles_to_fill_workers(self):
        # B=1, Cout=1024, 8 workers: untiled = 1 task (makespan 1024);
        # tile 128 = 8 tasks in parallel (makespan 128) — the model must
        # tile, and 128 is the narrowest lane-legal tile it may offer.
        assert choose_oc_tile(1, 1024, workers=8) == 128

    def test_narrow_cout_is_never_split_below_a_lane(self):
        # Cout=128 at B=1 would schedule best as 16-wide tiles, but a
        # 16-channel block is no TPU-legal lane tile: the layer stays whole
        assert choose_oc_tile(1, 128, workers=8) == 128

    def test_saturated_batch_prefers_big_tiles(self):
        # B=64 images already saturate 8 workers; splitting channels only
        # adds tasks without shortening the critical resource.
        assert choose_oc_tile(64, 32, workers=8) == 32

    def test_chosen_tile_schedules_no_worse_than_untiled(self):
        for batch, cout in [(1, 64), (2, 32), (5, 16)]:
            tile = choose_oc_tile(batch, cout, workers=8)
            def makespan(t, batch=batch, cout=cout):
                dag = TaskDAG()
                conv_grid_tasks(dag, batch, cout, t)
                return priority_schedule(dag, 8).makespan
            assert makespan(tile) <= makespan(cout) + 1e-9


class TestFCBlockModel:
    """G_FC at pallas-grid granularity + the choose_fc_block cost model
    (mirrors TestExecutedGrid for the dense kernel's task list)."""

    def test_grid_task_count_and_cost(self):
        dag = TaskDAG()
        tids = fc_grid_tasks(dag, d_out=64, block=16, cost_per_neuron=2.0)
        assert len(tids) == 64 // 16
        assert all(dag.tasks[t].cost == 32.0 for t in tids)

    def test_grid_rejects_non_divisor(self):
        with pytest.raises(ValueError):
            fc_grid_tasks(TaskDAG(), d_out=64, block=5)

    @settings(max_examples=30, deadline=None)
    @given(d_out=st.integers(1, 2048), workers=st.integers(1, 16))
    def test_choose_block_divides_d_out(self, d_out, workers):
        # the kernel pads the layer up to whole blocks, so a block either
        # IS the layer or is a lane multiple the padded layer divides
        block = choose_fc_block(d_out, workers=workers)
        assert block == d_out or (block % LANE == 0
                                  and block <= -(-d_out // LANE) * LANE)

    def test_small_d_out_stays_whole(self):
        # a layer narrower than a lane runs whole (a full-axis block)
        assert choose_fc_block(4) == 4
        assert choose_fc_block(10) == 10     # not a padded 128-lane block

    def test_wide_fc_blocks_to_fill_workers(self):
        # d_out=1024, 8 workers: block 512 = 2 tasks (makespan 512);
        # block 128 = 8 parallel tasks (makespan 128) — the model must split.
        assert choose_fc_block(1024, workers=8) == 128

    def test_padded_grid_counts_whole_blocks(self):
        # a lane-multiple block need not divide d_out: the executed grid
        # pads the layer up to ceil(d_out / block) full-cost tasks
        dag = TaskDAG()
        tids = fc_grid_tasks(dag, d_out=500, block=128)
        assert len(tids) == 4
        assert all(dag.tasks[t].cost == 128.0 for t in tids)

    @pytest.mark.parametrize("case", [f"case{i}" for i in range(1, 8)])
    def test_table2_blocks_are_tpu_legal(self, case):
        """Every tile the choosers can hand a Table-2 layer — the FC
        block, the dx grid's derived Din tile, the conv oc tile — is a
        LANE multiple or the whole axis (the Pallas TPU lowering refuses
        anything else)."""
        from repro.kernels.dense import _block_of
        from repro.models.cnn import _conv_shapes, make_case
        cfg = make_case(case)
        shapes, final = _conv_shapes(cfg)
        dims = ([final * final * cfg.filters]
                + [cfg.fc_neurons] * (cfg.fc_layers - 1) + [cfg.num_classes])
        for d_in, d_out in zip(dims[:-1], dims[1:]):
            for workers in (1, 4, 8, 16):
                block = choose_fc_block(d_out, workers=workers)
                assert block == d_out or block % LANE == 0, (d_out, block)
                tile = _block_of(d_in, block)
                assert tile == d_in or tile % LANE == 0, (d_in, tile)
        for batch in (1, 16, 64):
            tile = choose_oc_tile(batch, cfg.filters)
            assert tile == cfg.filters or tile % LANE == 0

    @settings(max_examples=60, deadline=None)
    @given(features=st.integers(1, 5000), d_out=st.integers(1, 5000))
    def test_derived_din_tile_is_tpu_legal(self, features, d_out):
        from repro.kernels.dense import _block_of
        for block in (0, choose_fc_block(d_out)):
            tile = _block_of(features, block)
            assert tile == features or tile % LANE == 0

    def test_single_worker_prefers_whole_layer(self):
        # serial makespans all equal d_out; the largest block wins the tie
        assert choose_fc_block(512, workers=1) == 512

    def test_chosen_block_schedules_no_worse_than_whole(self):
        for d_out in (64, 500, 1000):
            block = choose_fc_block(d_out, workers=8)

            def makespan(bl, d_out=d_out):
                dag = TaskDAG()
                fc_grid_tasks(dag, d_out, bl)
                return priority_schedule(dag, 8).makespan

            assert makespan(block) <= makespan(d_out) + 1e-9


class TestPriorities:
    def test_upstream_higher_than_downstream(self):
        dag = TaskDAG()
        a = dag.add("a", 1.0)
        b = dag.add("b", 1.0, deps=[a])
        c = dag.add("c", 1.0, deps=[b])
        dag.mark_priorities()
        assert dag.tasks[a].priority > dag.tasks[b].priority > \
            dag.tasks[c].priority

    def test_same_level_same_priority(self):
        dag = TaskDAG()
        a = dag.add("a", 1.0)
        b1 = dag.add("b1", 1.0, deps=[a])
        b2 = dag.add("b2", 2.0, deps=[a])
        dag.mark_priorities()
        assert dag.tasks[b1].priority == dag.tasks[b2].priority

    def test_cycle_detection(self):
        dag = TaskDAG()
        a = dag.add("a", 1.0, deps=[1])      # forward ref to b
        b = dag.add("b", 1.0, deps=[a])
        with pytest.raises(ValueError):
            dag.mark_priorities()


class TestSchedule:
    def _dag(self):
        return cnn_training_dag([
            {"kind": "conv", "hx": 8, "wx": 8, "hf": 3, "wf": 3, "depth": 3},
            {"kind": "pool", "hx": 8, "wx": 8, "k": 2},
            {"kind": "fc", "in": 128, "out": 64},
        ], tile=2)

    def test_bounds(self):
        dag = self._dag()
        for k in (1, 2, 4, 8):
            r = priority_schedule(dag, k)
            assert r.makespan >= r.critical_path - 1e-9
            assert r.makespan <= dag.total_work() + 1e-9
            assert r.speedup <= k + 1e-9

    def test_single_thread_is_serial(self):
        dag = self._dag()
        r = priority_schedule(dag, 1)
        assert r.makespan == pytest.approx(dag.total_work())
        assert r.speedup == pytest.approx(1.0)

    def test_more_threads_not_slower(self):
        dag = self._dag()
        m1 = priority_schedule(dag, 2).makespan
        m2 = priority_schedule(dag, 8).makespan
        assert m2 <= m1 + 1e-9

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 99), threads=st.integers(1, 12),
           n=st.integers(2, 40))
    def test_random_dags_complete(self, seed, threads, n):
        """Alg. 4.2 schedules every DAG completely and within bounds."""
        rng = np.random.default_rng(seed)
        dag = TaskDAG()
        tids = []
        for i in range(n):
            k = rng.integers(0, min(i, 3) + 1)
            deps = rng.choice(tids, size=k, replace=False) if tids and k else []
            tids.append(dag.add(f"t{i}", float(rng.random() + 0.1),
                                deps=list(deps)))
        r = priority_schedule(dag, threads)
        assert r.critical_path - 1e-9 <= r.makespan <= dag.total_work() + 1e-9
        # work conservation: busy time sums to total work
        assert r.thread_busy.sum() == pytest.approx(dag.total_work())
