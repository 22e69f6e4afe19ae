"""Raw file readers → normalized serialized datasets
(reference /root/reference/hydragnn/preprocess/raw_dataset_loader.py:29-388).

Formats:
  * LSMS / unit_test — text tables: line 0 = graph features, lines 1+ =
    per-node rows [feature, index, x, y, z, outputs...] (raw_dataset_loader.py:226-274).
  * CFG — AtomEye (extended) CFG crystal files + optional ``.bulk`` sidecar with
    graph features (raw_dataset_loader.py:161-224). The reference reads CFG via
    ase.io.cfg; ase is not available here, so ``cfg_io.read_cfg`` is our own parser.

Output contract (identical to reference, raw_dataset_loader.py:140-148): one pickle
file per split with three sequential dumps: minmax_node_feature [2, nfeat],
minmax_graph_feature [2, nfeat], then the list of samples. Min-max normalization is
computed globally across ALL splits (raw_dataset_loader.py:319-388).
"""

from __future__ import annotations

import os
import pickle
from typing import Dict, List

import numpy as np

from ..graphs.sample import GraphSample
from .cfg_io import read_cfg


def np_divide(x1, x2):
    return np.divide(x1, x2, out=np.zeros_like(x1), where=x2 != 0)


class RawDataLoader:
    """Parses raw files, normalizes, and pickles serialized splits (rank-0 only by
    the orchestration layer)."""

    def __init__(self, config: Dict):
        self.dataset_list: List[List[GraphSample]] = []
        self.serial_data_name_list: List[str] = []
        self.node_feature_name = config["node_features"]["name"]
        self.node_feature_dim = config["node_features"]["dim"]
        self.node_feature_col = config["node_features"]["column_index"]
        self.graph_feature_name = config["graph_features"]["name"]
        self.graph_feature_dim = config["graph_features"]["dim"]
        self.graph_feature_col = config["graph_features"]["column_index"]
        self.raw_dataset_name = config["name"]
        self.data_format = config["format"]
        self.path_dictionary = config["path"]

        assert len(self.node_feature_name) == len(self.node_feature_dim)
        assert len(self.node_feature_name) == len(self.node_feature_col)
        assert len(self.graph_feature_name) == len(self.graph_feature_dim)
        assert len(self.graph_feature_name) == len(self.graph_feature_col)

    # ---------------------------------------------------------------- public
    def load_raw_data(self) -> None:
        serialized_dir = os.path.join(
            os.environ["SERIALIZED_DATA_PATH"], "serialized_dataset"
        )
        os.makedirs(serialized_dir, exist_ok=True)

        for dataset_type, raw_data_path in self.path_dictionary.items():
            if not os.path.isabs(raw_data_path):
                raw_data_path = os.path.join(os.getcwd(), raw_data_path)
            if not os.path.exists(raw_data_path):
                raise ValueError("Folder not found: " + raw_data_path)
            files = sorted(os.listdir(raw_data_path))
            assert len(files) > 0, f"No data files provided in {raw_data_path}!"

            dataset = []
            for name in files:
                if name == ".DS_Store":
                    continue
                full = os.path.join(raw_data_path, name)
                if os.path.isfile(full):
                    obj = self._parse_file(full)
                    if obj is not None:
                        dataset.append(obj)
                elif os.path.isdir(full):
                    for sub in sorted(os.listdir(full)):
                        subf = os.path.join(full, sub)
                        if os.path.isfile(subf):
                            obj = self._parse_file(subf)
                            if obj is not None:
                                dataset.append(obj)

            if self.data_format == "LSMS":
                for s in dataset:
                    self._charge_density_update_for_lsms(s)
            dataset = self._scale_features_by_num_nodes(dataset)

            if dataset_type == "total":
                serial_data_name = self.raw_dataset_name + ".pkl"
            else:
                serial_data_name = f"{self.raw_dataset_name}_{dataset_type}.pkl"
            self.dataset_list.append(dataset)
            self.serial_data_name_list.append(serial_data_name)

        self._normalize_dataset()

        for serial_data_name, dataset in zip(
            self.serial_data_name_list, self.dataset_list
        ):
            # Installed whole by a rename: another process that trains the same
            # dataset may be reading the file this one is writing again.
            path = os.path.join(serialized_dir, serial_data_name)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "wb") as f:
                pickle.dump(self.minmax_node_feature, f)
                pickle.dump(self.minmax_graph_feature, f)
                pickle.dump(dataset, f)
            os.replace(tmp, path)

    # --------------------------------------------------------------- parsing
    def _parse_file(self, filepath):
        if self.data_format in ("LSMS", "unit_test"):
            return self._parse_lsms(filepath)
        if self.data_format == "CFG":
            return self._parse_cfg(filepath)
        raise ValueError(f"Unknown raw data format {self.data_format}")

    def _parse_lsms(self, filepath) -> GraphSample:
        with open(filepath, "r", encoding="utf-8") as f:
            lines = f.readlines()
        graph_feat = lines[0].split(None, 2)
        g_feature = []
        for item in range(len(self.graph_feature_dim)):
            for icomp in range(self.graph_feature_dim[item]):
                it_comp = self.graph_feature_col[item] + icomp
                g_feature.append(float(graph_feat[it_comp].strip()))

        node_feature_matrix = []
        node_position_matrix = []
        for line in lines[1:]:
            node_feat = line.split(None, 11)
            node_position_matrix.append(
                [float(node_feat[c].strip()) for c in (2, 3, 4)]
            )
            row = []
            for item in range(len(self.node_feature_dim)):
                for icomp in range(self.node_feature_dim[item]):
                    it_comp = self.node_feature_col[item] + icomp
                    row.append(float(node_feat[it_comp].strip()))
            node_feature_matrix.append(row)

        return GraphSample(
            x=np.asarray(node_feature_matrix, dtype=np.float32),
            pos=np.asarray(node_position_matrix, dtype=np.float32),
            y=np.asarray(g_feature, dtype=np.float32),
        )

    def _parse_cfg(self, filepath):
        if not filepath.endswith(".cfg"):
            return None
        cfg = read_cfg(filepath)
        sample = GraphSample(
            pos=cfg.positions.astype(np.float32),
            supercell_size=cfg.cell.astype(np.float32),
        )
        cols = [
            cfg.numbers.reshape(-1, 1),
            cfg.masses.reshape(-1, 1),
        ]
        for aux in ("c_peratom", "fx", "fy", "fz"):
            cols.append(cfg.aux[aux].reshape(-1, 1))
        sample.x = np.concatenate(cols, axis=1).astype(np.float32)

        bulk_path = os.path.splitext(filepath)[0] + ".bulk"
        if os.path.exists(bulk_path):
            with open(bulk_path, "r", encoding="utf-8") as f:
                graph_feat = f.readlines()[0].split(None, 2)
            g_feature = []
            for item in range(len(self.graph_feature_dim)):
                for icomp in range(self.graph_feature_dim[item]):
                    it_comp = self.graph_feature_col[item] + icomp
                    g_feature.append(float(graph_feat[it_comp].strip()))
            sample.y = np.asarray(g_feature, dtype=np.float32)
        return sample

    # ------------------------------------------------------------ transforms
    @staticmethod
    def _charge_density_update_for_lsms(sample: GraphSample) -> GraphSample:
        """Charge density column ← charge density − num protons
        (raw_dataset_loader.py:276-292)."""
        sample.x[:, 1] = sample.x[:, 1] - sample.x[:, 0]
        return sample

    def _scale_features_by_num_nodes(self, dataset):
        """Divide any ``*_scaled_num_nodes`` feature by the node count
        (raw_dataset_loader.py:294-317)."""
        g_idx = [
            i
            for i, nm in enumerate(self.graph_feature_name)
            if "_scaled_num_nodes" in nm
        ]
        n_idx = [
            i
            for i, nm in enumerate(self.node_feature_name)
            if "_scaled_num_nodes" in nm
        ]
        for s in dataset:
            if s.y is not None and g_idx:
                s.y[g_idx] = s.y[g_idx] / s.num_nodes
            if s.x is not None and n_idx:
                s.x[:, n_idx] = s.x[:, n_idx] / s.num_nodes
        return dataset

    def _normalize_dataset(self):
        """Global min-max across all splits; per logical feature (which may span
        multiple columns), matching raw_dataset_loader.py:319-388."""
        num_node_features = len(self.node_feature_dim)
        num_graph_features = len(self.graph_feature_dim)
        self.minmax_graph_feature = np.full((2, num_graph_features), np.inf)
        self.minmax_node_feature = np.full((2, num_node_features), np.inf)
        self.minmax_graph_feature[1, :] *= -1
        self.minmax_node_feature[1, :] *= -1

        for dataset in self.dataset_list:
            for s in dataset:
                g_start = 0
                for ifeat in range(num_graph_features):
                    g_end = g_start + self.graph_feature_dim[ifeat]
                    self.minmax_graph_feature[0, ifeat] = min(
                        float(s.y[g_start:g_end].min()),
                        self.minmax_graph_feature[0, ifeat],
                    )
                    self.minmax_graph_feature[1, ifeat] = max(
                        float(s.y[g_start:g_end].max()),
                        self.minmax_graph_feature[1, ifeat],
                    )
                    g_start = g_end
                n_start = 0
                for ifeat in range(num_node_features):
                    n_end = n_start + self.node_feature_dim[ifeat]
                    self.minmax_node_feature[0, ifeat] = min(
                        float(s.x[:, n_start:n_end].min()),
                        self.minmax_node_feature[0, ifeat],
                    )
                    self.minmax_node_feature[1, ifeat] = max(
                        float(s.x[:, n_start:n_end].max()),
                        self.minmax_node_feature[1, ifeat],
                    )
                    n_start = n_end

        for dataset in self.dataset_list:
            for s in dataset:
                g_start = 0
                for ifeat in range(num_graph_features):
                    g_end = g_start + self.graph_feature_dim[ifeat]
                    lo, hi = (
                        self.minmax_graph_feature[0, ifeat],
                        self.minmax_graph_feature[1, ifeat],
                    )
                    s.y[g_start:g_end] = np_divide(s.y[g_start:g_end] - lo, hi - lo)
                    g_start = g_end
                n_start = 0
                for ifeat in range(num_node_features):
                    n_end = n_start + self.node_feature_dim[ifeat]
                    lo, hi = (
                        self.minmax_node_feature[0, ifeat],
                        self.minmax_node_feature[1, ifeat],
                    )
                    s.x[:, n_start:n_end] = np_divide(s.x[:, n_start:n_end] - lo, hi - lo)
                    n_start = n_end
